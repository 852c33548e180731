"""From a profiler trace to the numbers the per-layer readers take.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a compact event list (kept as JSON for the recorded test trace):

    {"devices": {"<id>": {"ops": [[instr, start_ns, dur_ns, module, opcode]],
                          "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...],
     "scopes": {"<module>:<instr>": op_name},
     "edges": {"<module>:<instr>": [operand instr, ...]}}

On a TPU the "XLA Ops" line names each op by its HLO text
(``%fusion.12 = bf16[...] fusion(...)``): ``instr`` is the instruction's
name (``fusion.12``, or ``dim_agg_pallas.3`` for a Pallas kernel),
``opcode`` its HLO opcode, ``module`` the program run ("XLA Modules" line)
that encloses it.  Loop ops that only enclose others (``while``) are
dropped, so op times add up without counting a body twice.  ``host`` holds
the host's Python-thread spans: ``TraceAnnotation``s and, with names that
start with ``$``, the profiler's Python frames.  ``scopes`` maps an
instruction to the JAX scope path in its HLO metadata (``op_name``) and
``edges`` to the instructions it reads, from the compiled programs' text,
where a driver hands it over.
"""

from __future__ import annotations

import bisect
import collections
import gzip
import json
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%([\w.-]+) = .*?op_name="([^"]*)"')
_DEF = re.compile(r'^\s*(?:ROOT )?%([\w.-]+) = (.*)$')
_REF = re.compile(r'(?<![=\w])%([\w.-]+)')


def parse_op(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of one line of HLO text."""
    head, sep, rest = text.partition(" = ")
    instr = head.strip().lstrip("%")
    if not sep:
        return instr, ""
    i = 0
    if rest.startswith("("):                       # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    tail = rest[i:].lstrip()
    return instr, tail[:tail.find("(")] if "(" in tail else tail


def scope_map(hlo_text: str) -> tuple[dict, dict]:
    """From one compiled program's HLO text: ``"<module>:<instruction>"``
    -> its ``op_name`` metadata, and -> the instructions it reads."""
    module = hlo_text.split(None, 2)[1].rstrip(",") if hlo_text else ""
    scopes, edges = {}, {}
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if not m:
            continue
        key = f"{module}:{m.group(1)}"
        edges[key] = _REF.findall(m.group(2).split(", metadata=")[0])
        n = _OP_NAME.match(line)
        if n:
            scopes[key] = n.group(2)
    return scopes, edges


def compact_device(op_events, module_events) -> dict:
    """``op_events``/``module_events``: (HLO text or name, start, dur)."""
    mods = sorted(([str(n).split("(")[0], int(s), int(d)]
                   for n, s, d in module_events), key=lambda m: m[1])
    starts = [m[1] for m in mods]
    ev = sorted((int(s), int(d), str(t)) for t, s, d in op_events)
    ops = []
    for i, (s, d, text) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][0] < s + d and d > ev[i + 1][1]:
            continue                       # encloses the next op: a loop
        instr, opcode = parse_op(text)
        j = bisect.bisect_right(starts, s) - 1
        module = mods[j][0] if j >= 0 and s < mods[j][1] + mods[j][2] else ""
        ops.append([instr, s, d, module, opcode])
    return {"ops": ops, "modules": mods}


def read_xplane(path: str, hlo_texts=()) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(ev.name, ev.start_ns, ev.duration_ns)
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    mods = [(ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events]
            devices[plane.name.split(":")[-1]] = compact_device(ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend([str(ev.name), int(ev.start_ns),
                                 int(ev.duration_ns)] for ev in line.events)
    scopes, edges = {}, {}
    for text in hlo_texts:
        sc, ed = scope_map(text)
        scopes.update(sc)
        edges.update(ed)
    return {"devices": devices, "host": host, "scopes": scopes,
            "edges": edges}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of the intervals' union inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def is_collective(op) -> bool:
    return op[4].startswith(COLLECTIVES)


class Reduced:
    """A trace cut to a window ``[lo, hi]`` (ns, the trace's clock)."""

    def __init__(self, trace: dict, lo: float, hi: float, devices=None):
        self.lo, self.hi = lo, hi
        ids = sorted(trace["devices"], key=int)
        if devices is not None:
            ids = ids[:devices]
        self.devices = {i: trace["devices"][i] for i in ids}
        self.host = sorted(trace["host"], key=lambda h: h[1])
        self.scopes = trace.get("scopes", {})
        self.edges = trace.get("edges", {})
        self._in = {d: [o for o in self.devices[d]["ops"]
                        if o[1] + o[2] > lo and o[1] < hi]
                    for d in ids}

    def _clip(self, o) -> float:
        return (min(o[1] + o[2], self.hi) - max(o[1], self.lo)) * 1e-9

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, dev) -> float:
        return union_length([(o[1], o[1] + o[2]) for o in self._in[dev]],
                             self.lo, self.hi) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def scope(self, op) -> str:
        return self.scopes.get(f"{op[3]}:{op[0]}", "")

    def seconds_where(self, pred, dev=None) -> float:
        """Device seconds of the ops for which ``pred(reduced, op)`` holds,
        summed over the devices (or on one)."""
        return sum(self._clip(o) for d in ([dev] if dev is not None
                                           else self.devices)
                   for o in self._in[d] if pred(self, o))

    def kernel_seconds(self, prefix: str, dev=None) -> float:
        """Device seconds of the kernel calls whose instruction name starts
        with ``prefix``, together with the ops that write their operands
        and read their results: XLA may place a kernel's operands and
        result in on-chip memory, and then those neighbours move its bytes
        to and from HBM."""
        calls = {k for k in self.edges
                 if k.split(":", 1)[1].startswith(prefix)}
        near = set(calls)
        for k in calls:
            mod = k.split(":", 1)[0]
            near.update(f"{mod}:{o}" for o in self.edges[k])
        for k, ops in self.edges.items():
            mod = k.split(":", 1)[0]
            if any(f"{mod}:{o}" in calls for o in ops):
                near.add(k)
        return self.seconds_where(
            lambda r, o: o[0].startswith(prefix) or f"{o[3]}:{o[0]}" in near,
            dev)

    def module_runs(self, pred, dev=None) -> list[tuple[int, int]]:
        """(start, duration) of the program runs whose name matches."""
        d = dev if dev is not None else next(iter(self.devices))
        return [(s, dur) for name, s, dur in self.devices[d]["modules"]
                if pred(name) and s + dur > self.lo and s < self.hi]

    def collective_s(self, dev) -> float:
        return self.seconds_where(lambda r, o: is_collective(o), dev)

    def exposed_collective_s(self, dev) -> float:
        """Collective time on ``dev`` with no other op running there."""
        ops = self._in[dev]
        comp = [(o[1], o[1] + o[2]) for o in ops if not is_collective(o)]
        total = 0.0
        for o in ops:
            if is_collective(o):
                s, e = max(o[1], self.lo), min(o[1] + o[2], self.hi)
                if e > s:
                    total += (e - s) - union_length(comp, s, e)
        return total * 1e-9

    def name(self, op) -> str:
        """Readable name of an op: instruction, program, last scope part."""
        sc = self.scope(op).split("/")
        tail = "/".join(sc[-2:]) if sc != [""] else op[4]
        return f"{op[3]}:{op[0]} {tail}"[:160]

    def top_ops(self, top: int = 10) -> list[list]:
        """The ops that took the most device time, averaged over chips."""
        c = collections.Counter()
        for d in self.devices:
            for o in self._in[d]:
                c[self.name(o)] += self._clip(o)
        return [[n, v / len(self.devices)] for n, v in c.most_common(top)]

    def _host_names(self, times) -> list[str]:
        """For each of the sorted ``times``: the innermost annotation and
        the innermost Python frame open then (one sweep over the spans)."""
        out, i, active = [], 0, []
        for t in times:
            while i < len(self.host) and self.host[i][1] <= t:
                active.append(self.host[i])
                i += 1
            active = [h for h in active if h[1] + h[2] >= t]
            parts = []
            for frame in (False, True):
                inner = [h for h in active if h[0].startswith("$") == frame]
                if inner:
                    parts.append(min(inner, key=lambda h: h[2])[0])
            out.append(" / ".join(parts) or "no host span")
        return out

    def idle_gaps(self, dev=None, top: int = 10) -> list[list]:
        """Idle seconds on one device, grouped by what the host was doing
        at each gap's midpoint."""
        d = dev if dev is not None else next(iter(self.devices))
        gs = gaps([(o[1], o[1] + o[2]) for o in self._in[d]], self.lo, self.hi)
        names = self._host_names([(s + e) / 2 for s, e in gs])
        c = collections.Counter()
        for (s, e), n in zip(gs, names):
            c[n] += (e - s) * 1e-9
        return [[n, v] for n, v in c.most_common(top)]
