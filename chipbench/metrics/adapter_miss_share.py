"""Share of the window's adapter acquisitions that missed the device bank
and paged the adapter in (each a host-to-device copy, evicting the least
recently used adapter once the bank is full), from the store's pager
counters."""


def read(ctx):
    bank = ctx["info"].get("bank")
    if not bank or bank["hits"] + bank["misses"] == 0:
        return None
    return 100.0 * bank["misses"] / (bank["hits"] + bank["misses"])
