"""Multi-tenant adapter serving above capacity: ``ServingEngine.submit``
and ``step`` under an open-loop arrival schedule that outruns the engine,
FIFO, greedy decoding.

Set-up makes the base and every tenant's adapter on the device from the
seed, registers the adapters with an ``AdapterStore`` whose device bank
holds fewer of them than there are tenants, warms every program the
window uses (admission, chunked prefill, decode, page-in, and the
completion fetch at every count of finished rows), and fills the bank
from the tenants served before the window, so that the window's misses
evict.  The window submits each request when it falls due (the mix's
backlog at its start) and steps the engine until ``seconds`` have
passed; the queue grows all through it.
Its metric is the tokens generated in it, those of finished requests and
those decoded so far for requests still in flight, over its length.

The check runs the plain reference over a sample of the finished requests
drawn from the seed, the longest among them: prompt plus served tokens,
one forward pass each, and reads by how much each served token's logit
lies below the reference's best at its position.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, model, reference, traffic


class Run:
    def __init__(self, raw: dict, mix: dict, seed: int, chips: int, *,
                 traced: bool = False):
        self.raw, self.mix, self.seed, self.chips = raw, mix, seed, chips
        self.dm = model.dims(raw)
        self.traced = traced
        self.r_g = max(mix["ranks"])
        self.scale = mix["lora_alpha"] / self.r_g
        self.ranks = [mix["ranks"][i % len(mix["ranks"])]
                      for i in range(mix["tenants"])]
        self.dispatches: list[dict] = []

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.serving import AdapterStore, ServingEngine
        from repro.telemetry import Telemetry

        mix, dm = self.mix, self.dm
        t0 = time.perf_counter()
        self.base = jax.block_until_ready(model.init_weights(self.seed, dm))
        t1 = time.perf_counter()
        self.adapters = jax.device_get(model.init_adapters(
            self.seed, dm, self.ranks, self.r_g, mix["adapter_b_std"]))
        store = AdapterStore(slots=mix["bank_slots"], rank=self.r_g)
        for t in range(mix["tenants"]):
            # made finite from the seed: the store's host-side scan of
            # every tensor would only lengthen set-up
            store.register(t, self.adapter(t), self.ranks[t], validate=False)
        t2 = time.perf_counter()
        tel = Telemetry(enabled=True, annotate=True) if self.traced else None
        self.engine = eng = ServingEngine(
            model.model_config(self.raw, "bench"), self.base, store,
            lora_scale=self.scale, max_slots=mix["max_slots"],
            max_prompt=mix["prompt"]["max"], max_gen=mix["output"]["max"],
            prefill_chunk=mix["prefill_chunk"],
            lora_backend=mix["lora_backend"], telemetry=tel)
        warm = traffic.warm_requests(mix, dm["vocab"], self.seed)
        eng.run([self._request(w) for w in warm])
        # the completion fetch gathers the finished rows: one shape per count
        for n in range(1, mix["max_slots"] + 1):
            idx = np.arange(n)
            jax.block_until_ready((eng._state["gen"][idx],
                                   eng._state["fault"][idx]))
        eng.reset()
        for t in traffic.bank_history(mix, self.seed):
            store.acquire(t)
            store.release(t)
        jax.block_until_ready(store.scan_stack)
        self.diagnostics = {"setup_phases_s": {
            "weights": t1 - t0, "adapters": t2 - t1,
            "engine_and_warmup": time.perf_counter() - t2}}
        self.hlo_texts = []
        if self.traced:
            args = (eng.params, eng.store.scan_stack, eng._state, eng._cache)
            self.hlo_texts = [f.lower(*args).compile().as_text()
                              for f in (eng._step_fn, eng._prefill_fn)]
            self._count_dispatches()

    def adapter(self, t: int) -> dict:
        return {n: {m: e[m][t] for m in ("A", "B")}
                for n, e in self.adapters.items()}

    def _request(self, spec: dict):
        from repro.serving.engine import Request

        return Request(adapter_id=spec["tenant"],
                       prompt_tokens=spec["prompt"], gen_len=spec["gen_len"])

    def _count_dispatches(self) -> None:
        """Traced runs only: wrap the engine's decode and prefill programs
        to record, per dispatch, the rows it advances (first position,
        positions, rank) and the adapters it gathers, from the engine's
        host-side slot mirrors."""
        eng, C = self.engine, self.mix["prefill_chunk"]
        step_fn, prefill_fn = eng._step_fn, eng._prefill_fn
        progress: dict = {}

        def tenant(s):
            return eng._requests[s].adapter_id

        def step(*a):
            rows = [(int(eng._pos_h[s]), 1, tenant(s))
                    for s in eng.busy_slots
                    if eng._pos_h[s] < eng._tlen_h[s]]
            self._record("serve_step", rows, True)
            return step_fn(*a)

        def prefill(*a):
            rows = []
            for s in eng.busy_slots:
                req, fill = eng._requests[s], int(eng._plen_h[s]) - 1
                if eng._pos_h[s] != 0:
                    progress.pop(s, None)
                    continue
                uid, done = progress.get(s, (req.uid, 0))
                if uid != req.uid:
                    done = 0
                n = min(C, fill - done)
                if n > 0:
                    rows.append((done, n, tenant(s)))
                progress[s] = (req.uid, done + max(n, 0))
            self._record("prefill_step", rows, False)
            return prefill_fn(*a)

        eng._step_fn, eng._prefill_fn = step, prefill

    def _record(self, kind: str, rows, decode: bool) -> None:
        """``rows``: (first position, positions, tenant) per slot."""
        tenants = {t for _, _, t in rows}
        fl, by = flops.serve_dispatch(
            self.dm, [(p, n, self.ranks[t]) for p, n, t in rows],
            [self.ranks[t] for t in tenants], decode=decode)
        m = self.mix["max_slots"] * (1 if decode else self.mix["prefill_chunk"])
        self.dispatches.append({"kind": kind, "flops": fl, "bytes": by,
                                "rows": m, "adapters": len(tenants)})

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> dict:
        eng, store = self.engine, self.engine.store
        reqs = traffic.serve_requests(self.mix, self.dm["vocab"], self.seed,
                                      seconds)
        self.requests = reqs
        by_uid, late, errors = {}, [], 0
        self.served: dict[int, np.ndarray] = {}
        pager0 = dict(store.paging_stats)
        disp0 = dict(eng.dispatch_count)
        bursts0 = len(eng.prefill_bursts)
        i, n = 0, len(reqs)
        t0 = time.perf_counter()
        while (now := time.perf_counter() - t0) < seconds:
            while i < n and reqs[i]["due"] <= now:
                uid = eng.submit(self._request(reqs[i]))
                late.append(now - reqs[i]["due"])
                by_uid[uid] = i
                i += 1
            if eng.queue or eng.busy_slots:
                for rec in eng.step():
                    if rec["status"] == "ok":
                        self.served[by_uid[rec["uid"]]] = rec["tokens"]
                    else:
                        errors += 1
            else:
                nxt = reqs[i]["due"] if i < n else seconds
                time.sleep(min(max(nxt - now, 0.0), 1e-3))
        jax.block_until_ready(eng._state)
        self.window_s = time.perf_counter() - t0
        # tokens of requests still in flight: decode steps past the prompt
        in_flight = sum(max(int(eng._pos_h[s] - eng._plen_h[s]) + 1, 0)
                        for s in eng.busy_slots)
        tokens = sum(len(t) for t in self.served.values()) + in_flight
        late.sort()
        self.generator = {"late_p95_s": late[int(0.95 * (len(late) - 1))]
                          if late else 0.0, "late_max_s": late[-1] if late
                          else 0.0, "requests": n}
        pager = store.paging_stats
        self.bank = {k: pager[k] - pager0[k]
                     for k in ("hits", "misses", "evictions")}
        self.diagnostics.update(
            bank=self.bank, finished=len(self.served),
            in_flight=len(eng.busy_slots), queued=len(eng.queue),
            submitted=i, in_flight_tokens=in_flight,
            dispatches={k: v - disp0.get(k, 0)
                        for k, v in eng.dispatch_count.items()},
            prefill_bursts=len(eng.prefill_bursts) - bursts0)
        return {"attempted": i, "failed": errors,
                "metrics": {"out_tokens_per_s": (tokens / self.window_s,
                                                 "tokens/s")}}

    def layer_info(self) -> dict:
        return {"kind": "serve", "dispatches": self.dispatches,
                "hlo_texts": self.hlo_texts,
                "generator": self.generator, "bank": self.bank,
                "layers": self.dm["layers"],
                "bgmv_shapes": flops.lora_site_dims(self.dm),
                "bank_rank": self.r_g}

    def free(self) -> None:
        del self.engine

    # ------------------------------------------------------------ check
    def sample(self) -> list[int]:
        """Finished requests to check: the longest, then others drawn from
        the seed until ``check_tokens`` served tokens are covered."""
        done = sorted(self.served)
        if not done:
            return []
        size = lambda q: len(self.requests[q]["prompt"]) + len(self.served[q])
        longest = max(done, key=size)
        rest = [q for q in np.random.default_rng([7, self.seed]).permutation(
            done) if q != longest]
        out, toks = [longest], len(self.served[longest])
        for q in rest:
            if toks >= self.mix["check_tokens"]:
                break
            out.append(int(q))
            toks += len(self.served[q])
        return out

    def check(self, prec: str = "f32") -> dict:
        """Widest gap between the reference's best logit and that of the
        token served (``prec="f32"``), or, for the control, of the token
        the reference in ``prec`` ranks first, over the sampled requests."""
        fn = reference.make_served_gap(self.dm, self.scale, prec)
        G = self.mix["output"]["max"]
        S = self.mix["prompt"]["max"] + G
        gap = 0.0
        for q in self.sample():
            prompt, gen = self.requests[q]["prompt"], self.served[q]
            seq = np.zeros((1, S), np.int32)
            body = np.concatenate([prompt, gen[:-1]])
            seq[0, :len(body)] = body
            at = np.zeros((G,), np.int32)
            at[:len(gen)] = np.arange(len(prompt) - 1,
                                      len(prompt) - 1 + len(gen))
            served = np.zeros((G,), np.int32)
            served[:len(gen)] = gen
            ad = jax.tree_util.tree_map(
                jnp.asarray, self.adapter(self.requests[q]["tenant"]))
            gap = max(gap, float(fn(self.base, ad, jnp.asarray(seq),
                                    jnp.asarray(at), jnp.asarray(served),
                                    jnp.arange(G) < len(gen))))
        return {"logit_gap": gap}
