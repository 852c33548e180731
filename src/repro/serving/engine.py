"""Continuous-batching inference engine over heterogeneous-rank adapters.

Execution model
---------------

The engine owns ``max_slots`` *slots*.  A slot is one row of every batched
buffer: one row of the rectangular KV cache (``init_cache`` layout, batch
axis 1 — per-slot occupancy is *ragged*: each slot sits at its own ``pos``
and everything past it is masked), one row of the prompt/vision staging
buffers, one adapter-bank index.  The decode loop is:

1. **admit** — free slots are filled from the request queue *every step*
   (continuous batching), not only when the whole batch drains.  Admission
   pins the request's adapter in the :class:`~repro.serving.adapter_store.
   AdapterStore` (paging it in if cold), stages the prompt tokens plus the
   request's *projected* vision-prefix vectors (the ``vision_proj`` matmul
   runs once here, not per step) into the slot's device buffers and zeroes
   the slot's cache rows — one small jitted scatter per admitted request
   (``serve_admit``).  With ``prefill_chunk`` set, the whole ready burst
   is admitted first and then filled by **shared chunked prefill**:
   ``max_s ⌈P_s/chunk⌉`` ``serve_prefill`` dispatches
   (``repro.launch.steps.make_chunked_prefill_step``) each push up to
   ``chunk`` teacher-forced positions of EVERY prefill-phase slot through
   the decode-cache write path in one program — no logits, intra-chunk
   causal attention at each slot's ragged offset — so same-step admissions
   share dispatches (vs the per-request ``Σ_s ⌈P_s/chunk⌉``) and a freshly
   admitted long prompt never steals decode steps from active slots.
2. **step** — ONE jitted dispatch (``serve_step``) advances every occupied
   slot by one token.  Inside the program each slot muxes its own input:
   vision-prefix vector while ``pos < n_prefix``, teacher-forced prompt
   token while ``pos < plen``, else the slot's last generated token; the
   batched multi-adapter decode
   (``repro.launch.steps.make_multi_adapter_serve_step``) applies each
   row's adapter from the store's stacked bank by index (BGMV — per-site
   gathered (A, B) pairs, or the Pallas scalar-prefetch gather kernel with
   ``lora_backend="grouped"``) and runs the batched KV-cached decode at
   per-row positions; next tokens (greedy, or temperature/top-k sampled
   from per-slot PRNG keys when ``sampling`` is set) are written into the
   slot's generation buffer in-program.  Without ``prefill_chunk``, prefill
   is *streamed through the decode step* (one position per step) — the
   legacy baseline ``benchmarks/bench_serving.py`` measures chunked prefill
   against.
3. **retire** — the host tracks every slot's position mirror (positions
   advance deterministically, so scheduling needs NO device fetch); slots
   whose request finished are fetched (one gather for all completions of
   the step), their adapters unpinned, and the slots returned to the pool.

What is fetched when: nothing per step — generated tokens cross to host
only when a request completes.  ``dispatch_count`` tallies ``serve_step``
(exactly one per decode step — asserted by tests), ``serve_prefill``
(exactly ``max_s ⌈P_s/chunk⌉`` per admission burst, recorded in
``prefill_bursts`` and asserted), ``serve_admit``, ``adapter_load`` and
``fetch``.  The counters ``serving.prefill_tokens`` (prompt positions
filled) and ``serving.prefill_rows`` (rows the prefill program computed,
``max_slots × prefill_chunk`` per dispatch) give the prefill row use,
their ratio.  Completion records carry
``latency_s`` and ``ttft_s`` (submit → the step() call that emitted the
request's first token; dispatch-clock, not device-sync — the scheduling
delay chunked prefill attacks).

Fault containment and cancellation
----------------------------------

A shared dispatch must not let one tenant take down the batch:

* **non-finite logits** — each step flags rows whose logits contain
  NaN/Inf (a corrupt adapter, a poisoned cache) in a sticky per-slot
  ``fault`` bit carried in engine state, and emits token 0 for them so
  the faulted row cannot propagate non-finite values into ``last`` /
  ``gen``.  Decoding is row-independent (per-row adapter gather, per-row
  cache rows), so every OTHER slot's tokens are bit-identical to a clean
  run — asserted by tests and ``bench_serving --quick-slo``.  Fault flags
  ride the SAME completion fetch (one ``device_get`` per retire burst);
  faulted requests complete with ``status="error"``.
* **cancellation** (:meth:`ServingEngine.cancel` /
  :meth:`~ServingEngine.cancel_slot`) — freeing a slot is pure host
  bookkeeping: the request detaches, its adapter unpins, and the host
  mirrors zero.  The device row keeps advancing inside the shared
  program until re-admission overwrites it (harmless: rows are
  independent and admission resets all slot state), so cancelling adds
  ZERO dispatches and never splits the fused step.  Cancelled/timed-out/
  shed requests increment ``serving.cancelled`` / ``serving.timeout`` /
  ``serving.shed`` counters and are excluded from the TTFT/latency/
  queue-wait histograms (ok-status completions only — overload must not
  flatter the percentiles).

``Request`` carries an SLO class (``slo``: ``"interactive"`` | ``"batch"``)
and optional deadline; the engine itself stays policy-free FIFO — deadline
scheduling, backpressure and shedding live in
:mod:`repro.serving.scheduler`, which reorders ``engine.queue`` and drives
cancellation through the public hooks above.  The engine reads time from
``self.clock`` (default ``time.perf_counter``) so schedulers can inject a
virtual clock for deterministic overload tests.

Static-batching mode (``continuous=False``) admits only when ALL slots are
free — the classic serve-a-batch-then-drain baseline that
``benchmarks/bench_serving.py`` measures continuous batching against.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.steps import (make_chunked_prefill_step,
                                make_multi_adapter_serve_step)
from repro.models import transformer as T
from repro.models.config import ModelConfig

from repro.serving.adapter_store import (AdapterQuarantinedError,
                                         AdapterStore)
from repro.telemetry import Telemetry

Pytree = Any
_UIDS = itertools.count()

#: request SLO classes, highest priority first (the scheduler admits
#: interactive ahead of batch; the engine only labels metrics/spans by it)
SLO_CLASSES = ("interactive", "batch")


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Opt-in stochastic decoding: logits are scaled by ``1/temperature``,
    optionally truncated to the ``top_k`` largest, and sampled with a
    per-slot PRNG key carried in engine state (seeded from the engine's
    ``sample_seed`` folded with the request uid at admission, so a given
    request's tokens are reproducible).  Greedy (``sampling=None``) stays
    the default and the exactness-tested path; ``top_k=1`` degenerates to
    greedy (tested)."""

    temperature: float = 1.0
    top_k: int = 0                     # 0 = full vocabulary


@dataclasses.dataclass(eq=False)
class Request:
    """One inference request: decode ``gen_len`` tokens after the
    teacher-forced ``prompt_tokens`` (and, for prefix-VLMs, the projected
    ``vision`` patches), through adapter ``adapter_id``.

    Identity equality (``eq=False``): a request IS its uid, and field-wise
    comparison would trip over the numpy payloads (ambiguous array truth
    in ``list.remove`` — the scheduler manages pending sets by identity)."""

    adapter_id: Any
    prompt_tokens: np.ndarray          # i32 [P_t]
    gen_len: int
    vision: np.ndarray | None = None   # f32 [P, Dv]
    uid: int = dataclasses.field(default_factory=lambda: next(_UIDS))
    submitted_at: float = 0.0
    admitted_at: float | None = None
    first_token_at: float | None = None
    # ---- SLO fields (consumed by repro.serving.scheduler; plain-engine
    # runs leave them at their defaults and behave exactly as before) ----
    slo: str = "batch"                 # "interactive" | "batch"
    deadline_s: float | None = None    # relative SLO; None = class default
    deadline_at: float | None = None   # absolute, stamped by the scheduler
    status: str = "ok"                 # ok | error | shed | timeout | cancelled
    attempts: int = 0                  # submit attempts (retry-with-backoff)
    degraded: bool = False             # gen_len clamped by the shed policy


class ServingEngine:
    """Multi-tenant continuous-batching decode over an :class:`AdapterStore`.

    Supports decoder stacks whose cache rows are per-slot resettable
    (self-attention KV, sliding-window rings, Mamba states) — i.e. the
    ``attn`` / ``attn_local`` / ``mamba`` sublayers; precomputed
    cross-attention caches and the enc-dec family are rejected at
    construction (their K/V depend on per-request encoder runs, which the
    slot-reset scatter cannot rebuild).
    """

    def __init__(self, cfg: ModelConfig, params: Pytree, store: AdapterStore,
                 *, lora_scale: float, max_slots: int = 8,
                 max_prompt: int = 32, max_gen: int = 32,
                 use_vision: bool | None = None, continuous: bool = True,
                 prefill_chunk: int | None = None,
                 prefill_flash: bool | None = None,
                 lora_backend: str = "gather",
                 sampling: SamplingConfig | None = None,
                 sample_seed: int = 0, mesh=None,
                 telemetry: Telemetry | None = None):
        """``mesh``: optional serving mesh — a 1-D ``("data",)`` mesh
        shards the SLOT axis (decode-cache batch rows, slot-state rows,
        adapter bank) over its devices via ``sharding.cache_spec`` /
        ``batch_spec``, exactly like the federated round shards its client
        axis; a 2-D ``("data", "model")`` mesh additionally places the
        base weights tensor-parallel via ``param_spec_tp`` (TP only —
        never FSDP over the slot axis).  Token-identical to the unsharded
        engine (tested).  Slot-axis sharding requires ``max_slots`` to
        divide over ``"data"``."""
        bad = {k for k in cfg.pattern if k not in ("attn", "attn_local",
                                                   "mamba")}
        if bad or cfg.family == "encdec":
            raise NotImplementedError(
                f"serving engine supports attn/attn_local/mamba stacks, got "
                f"pattern {cfg.pattern} family {cfg.family}")
        if lora_backend not in ("gather", "grouped"):
            raise ValueError(f"lora_backend {lora_backend!r} not in "
                             "('gather', 'grouped')")
        if sampling is not None and sampling.temperature <= 0:
            raise ValueError("sampling.temperature must be > 0 "
                             "(use sampling=None for greedy)")
        self.cfg = cfg
        self.params = params
        self.store = store
        self.lora_scale = lora_scale
        self.max_slots = max_slots
        self.max_prompt = max_prompt
        self.max_gen = max_gen
        self.continuous = continuous
        self.lora_backend = lora_backend
        self.sampling = sampling
        self.sample_seed = sample_seed
        if use_vision is None:
            use_vision = cfg.family == "vlm" and cfg.vision_mode == "prefix"
        self._n_prefix = cfg.num_vision_tokens if use_vision else 0
        self.cache_len = self._n_prefix + max_prompt + max_gen
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{prefill_chunk}")
            if "mamba" in cfg.pattern:
                raise NotImplementedError(
                    "chunked prefill needs positional cache rows; a mamba "
                    "state is recurrent — use streamed prefill "
                    "(prefill_chunk=None) for mamba stacks")
            if "attn_local" in cfg.pattern and cfg.sliding_window:
                ring = min(self.cache_len, cfg.sliding_window)
                if prefill_chunk > ring:
                    raise ValueError(
                        f"prefill_chunk {prefill_chunk} exceeds the local "
                        f"layers' ring cache ({ring} rows) — per-row "
                        "scatter indices would collide")
                max_fill = self._n_prefix + max_prompt - 1
                if prefill_chunk > 1 and max_fill > ring:
                    raise ValueError(
                        f"chunked prefill would wrap the local layers' "
                        f"ring cache: up to {max_fill} teacher-forced "
                        f"positions vs {ring} ring rows.  A chunk writes "
                        "all its K/V rows before attending, so a write at "
                        "position p >= ring overwrites the slot holding "
                        "p-ring, which earlier queries of the SAME chunk "
                        "still need (any p-ring is inside their window "
                        "because ring <= window) — tokens would silently "
                        "diverge from streamed decode.  Shrink max_prompt, "
                        "grow the window, or use streamed prefill "
                        "(prefill_chunk=None)")
        self.prefill_chunk = prefill_chunk
        self.mesh = mesh
        if mesh is None and getattr(store, "mesh", None) is not None:
            raise ValueError(
                "AdapterStore carries a serving mesh but the engine is "
                "unsharded — pass the same mesh to ServingEngine too "
                "(a mesh-committed bank feeding an unsharded dispatch "
                "fails with an opaque incompatible-devices error)")
        if mesh is not None:
            if "data" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'data' axis for the slot "
                    f"dimension, got axes {tuple(mesh.axis_names)}")
            if max_slots % mesh.shape["data"] != 0:
                raise ValueError(
                    f"max_slots={max_slots} does not divide over the "
                    f"mesh's data axis ({mesh.shape['data']} devices)")
            from repro import sharding as SH
            # frozen base weights: TP over "model" when the mesh carries
            # one, replicated otherwise — NEVER FSDP over "data" (that
            # axis is the SLOT axis here; data-sharded frozen weights
            # would all-gather per decode step)
            self.params = params = jax.device_put(
                params, SH.tree_param_shardings(params, mesh,
                                                spec_fn=SH.param_spec_tp))
            if store.mesh is None:
                # adopt + re-place: the bank may already be materialised
                # on the default device (store shared with an unsharded
                # engine first)
                store.set_mesh(mesh)
            elif store.mesh is not mesh:
                raise ValueError(
                    "AdapterStore was built for a different mesh than the "
                    "engine's — pass the SAME mesh to both (mixed "
                    "placements would crash the jitted decode dispatch)")

        B = max_slots
        self._cache = T.init_cache(cfg, params, B, self.cache_len)
        if mesh is not None:
            from repro import sharding as SH
            # decode cache: batch (slot) rows over "data", feature dims
            # over "model" where divisible — the cache_spec baseline rules
            self._cache = jax.device_put(
                self._cache, SH.tree_cache_shardings(self._cache, mesh))
        state = {
            "ptoks": jnp.zeros((B, max_prompt), jnp.int32),
            "aidx": jnp.zeros((B,), jnp.int32),
            "pos": jnp.zeros((B,), jnp.int32),
            "plen": jnp.zeros((B,), jnp.int32),
            "tlen": jnp.zeros((B,), jnp.int32),   # 0 = slot free/inactive
            "last": jnp.zeros((B,), jnp.int32),
            "gen": jnp.zeros((B, max_gen), jnp.int32),
            # sticky per-slot fault bit: set when a step sees non-finite
            # logits for the row, cleared at (re-)admission — rides the
            # completion fetch so fault detection costs zero extra syncs
            "fault": jnp.zeros((B,), jnp.bool_),
        }
        if self._n_prefix:
            # PROJECTED prefix vectors [P, d_model], not raw patches: the
            # projection runs once per request at admit time, not per step
            state["vis"] = jnp.zeros(
                (B, cfg.num_vision_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
        if sampling is not None:
            state["rng"] = jnp.zeros((B, 2), jnp.uint32)  # per-slot PRNG key
        if mesh is not None:
            from repro import sharding as SH
            # slot-state rows over "data" (batch_spec: dim 0 when divisible)
            state = jax.device_put(state, SH.tree_batch_shardings(state, mesh))
        self._state = state
        self._step_fn = jax.jit(self._build_step(), donate_argnums=(2, 3))
        self._admit_fn = jax.jit(self._build_admit(), donate_argnums=(1, 2))
        self._prefill_fn = None
        if prefill_chunk is not None:
            self._prefill_fn = jax.jit(
                make_chunked_prefill_step(
                    cfg, lora_scale=lora_scale, chunk=prefill_chunk,
                    n_prefix=self._n_prefix, lora_backend=lora_backend,
                    bank_layout="scan", flash=prefill_flash),
                donate_argnums=(2, 3))

        # host mirrors (scheduling never fetches device state)
        self._requests: list[Request | None] = [None] * B
        self._pos_h = np.zeros((B,), np.int64)
        self._plen_h = np.zeros((B,), np.int64)
        self._tlen_h = np.zeros((B,), np.int64)
        self.queue: collections.deque[Request] = collections.deque()
        self.completed: list[dict] = []
        self._admit_failed: list[dict] = []   # quarantine failures this step
        self.steps = 0
        # injectable time source: schedulers swap in a virtual clock so
        # deadline/timeout behaviour is testable without wall-clock races
        self.clock = time.perf_counter
        # one record per shared-prefill burst: the admitted slots' fill
        # lengths and the max-⌈P/chunk⌉ dispatches that covered them all
        self.prefill_bursts: list[dict] = []
        self.dispatch_count: collections.Counter = store.dispatch_count
        self.telemetry = (telemetry if telemetry is not None
                          else Telemetry(enabled=False))
        if telemetry is not None and not store.telemetry.enabled:
            store.use_telemetry(telemetry)   # one registry for both
        m = self.telemetry.metrics
        m.counter_group("serving.dispatch", self.dispatch_count)
        self._h_ttft = m.histogram("serving.ttft_seconds")
        self._h_latency = m.histogram("serving.latency_seconds")
        self._h_queue_wait = m.histogram("serving.queue_wait_seconds")
        self._c_tokens = m.counter("serving.generated_tokens")
        self._c_completed = m.counter("serving.completed_requests")
        # chunked prefill's work: prompt positions filled, and the rows the
        # prefill program computed for them (every dispatch computes all
        # max_slots × prefill_chunk rows); their ratio is the row use
        self._c_prefill_tokens = m.counter("serving.prefill_tokens")
        self._c_prefill_rows = m.counter("serving.prefill_rows")
        # overload/fault accounting: these are the ONLY places rejected /
        # shed / timed-out / faulted requests show up — they never touch
        # the TTFT/latency/queue-wait histograms above
        self._c_shed = m.counter("serving.shed")
        self._c_timeout = m.counter("serving.timeout")
        self._c_cancelled = m.counter("serving.cancelled")
        self._c_errors = m.counter("serving.request_errors")
        m.gauge_fn("serving.queue_depth", lambda: float(len(self.queue)))
        for cls in SLO_CLASSES:
            # per-class depth over the engine queue; an SLOScheduler
            # re-registers these over its own pending set (latest wins)
            m.gauge_fn(f"serving.queue_depth.{cls}",
                       lambda c=cls: float(sum(1 for r in self.queue
                                               if r.slo == c)))
        m.gauge_fn("serving.slot_occupancy",
                   lambda: len(self.busy_slots) / self.max_slots)

    # ------------------------------------------------------------ step fns
    def _build_step(self):
        cfg, n_prefix = self.cfg, self._n_prefix
        Sp, max_gen = self.max_prompt, self.max_gen
        sampling = self.sampling
        # the engine feeds store.scan_stack (scan-major [L, G, ...],
        # re-transposed only on page-in) so no dispatch transposes the bank
        serve = make_multi_adapter_serve_step(cfg, lora_scale=self.lora_scale,
                                              lora_backend=self.lora_backend,
                                              bank_layout="scan")

        def serve_step(params, adapters, state, cache):
            pos, plen, tlen = state["pos"], state["plen"], state["tlen"]
            last = state["last"]
            active = pos < tlen
            # ---- per-slot input mux: prefix vector | prompt token | last --
            tok_pos = jnp.clip(pos - n_prefix, 0, Sp - 1)
            prompt_tok = jnp.take_along_axis(state["ptoks"], tok_pos[:, None],
                                             axis=1)[:, 0]
            tok = jnp.where(pos < plen, prompt_tok, last)
            embeds = params["embed"][tok]                       # [B, d]
            if n_prefix:
                rows = jnp.arange(pos.shape[0])
                pre = state["vis"][rows, jnp.clip(pos, 0, n_prefix - 1)]
                embeds = jnp.where((pos < n_prefix)[:, None],
                                   pre.astype(embeds.dtype), embeds)
            # ---- batched multi-adapter decode (per-row adapter + pos) -----
            logits, cache = serve(params, adapters, state["aidx"], cache,
                                  embeds, pos)
            # ---- fault containment: a row whose logits went non-finite
            # (corrupt adapter, poisoned cache) is flagged sticky and its
            # emitted token pinned to 0 — argmax/categorical over NaN is
            # undefined but the OTHER rows never see it (row-independent
            # decode), so they stay bit-identical to a clean run
            bad = ~jnp.isfinite(logits).all(axis=-1)
            fault = state["fault"] | (bad & active)
            if sampling is None:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                # per-slot keys: split once per step, sample each row with
                # its own subkey, carry the rest — fully in-program
                ks = jax.vmap(lambda k: jax.random.split(k, 2))(state["rng"])
                sub, state = ks[:, 0], dict(state, rng=ks[:, 1])
                lg = logits / sampling.temperature
                if sampling.top_k:
                    kth = jax.lax.top_k(lg, sampling.top_k)[0][:, -1:]
                    lg = jnp.where(lg >= kth, lg, -1e30)
                nxt = jax.vmap(jax.random.categorical)(sub, lg).astype(
                    jnp.int32)
            nxt = jnp.where(fault, 0, nxt)
            # ---- emit into the slot's generation buffer -------------------
            g = pos - (plen - 1)                # generated-token index
            ok = active & (g >= 0) & (g < max_gen)
            rows = jnp.arange(pos.shape[0])
            cg = jnp.clip(g, 0, max_gen - 1)
            gen = state["gen"].at[rows, cg].set(
                jnp.where(ok, nxt, state["gen"][rows, cg]))
            last = jnp.where(ok, nxt, last)
            pos = pos + active.astype(pos.dtype)
            return dict(state, pos=pos, last=last, gen=gen,
                        fault=fault), cache

        return serve_step

    def _build_admit(self):
        vlm = bool(self._n_prefix)
        sampled = self.sampling is not None

        def admit(params, state, cache, slot, ptoks, vis, aidx, plen, tlen,
                  rng):
            st = dict(state)
            st["ptoks"] = state["ptoks"].at[slot].set(ptoks)
            if vlm:
                # project the prefix ONCE here (exactly what
                # make_greedy_generate does at prefill) — the decode step
                # then just gathers the slot's precomputed [P, d] rows
                dt = state["vis"].dtype
                pre = vis.astype(dt) @ params["vision_proj"].astype(dt)
                st["vis"] = state["vis"].at[slot].set(pre)
            if sampled:
                st["rng"] = state["rng"].at[slot].set(rng)
            st["aidx"] = state["aidx"].at[slot].set(aidx)
            st["fault"] = state["fault"].at[slot].set(False)
            st["pos"] = state["pos"].at[slot].set(0)
            st["plen"] = state["plen"].at[slot].set(plen)
            st["tlen"] = state["tlen"].at[slot].set(tlen)
            st["last"] = state["last"].at[slot].set(0)
            st["gen"] = state["gen"].at[slot].set(0)
            # reset the slot's ragged cache row (batch axis 1 in every leaf):
            # zero state is exactly a fresh init_cache row for KV and Mamba
            cache = jax.tree_util.tree_map(
                lambda c: c.at[:, slot].set(jnp.zeros((), c.dtype)), cache)
            return st, cache

        return admit

    # ------------------------------------------------------------ scheduling
    @property
    def busy_slots(self) -> list[int]:
        return [s for s in range(self.max_slots)
                if self._requests[s] is not None]

    def validate(self, req: Request) -> None:
        """Reject a bad request up front (raises; never touches the queue).
        Split from :meth:`submit` so schedulers can validate before
        applying their own admission policy."""
        if not 1 <= len(req.prompt_tokens) <= self.max_prompt:
            raise ValueError(
                f"prompt of {len(req.prompt_tokens)} tokens outside "
                f"[1, max_prompt={self.max_prompt}] — the first generated "
                "token comes from the last prompt position, so an empty "
                "prompt would condition on a fabricated token 0 and never "
                "fill gen[0]")
        if not 1 <= req.gen_len <= self.max_gen:
            raise ValueError(f"gen_len {req.gen_len} outside "
                             f"[1, max_gen={self.max_gen}]")
        if req.slo not in SLO_CLASSES:
            raise ValueError(f"request {req.uid}: slo {req.slo!r} not in "
                             f"{SLO_CLASSES}")
        if req.adapter_id in self.store.quarantined:
            raise AdapterQuarantinedError(
                f"adapter {req.adapter_id!r} is quarantined: "
                f"{self.store.quarantined[req.adapter_id]}")
        if req.adapter_id not in self.store:
            raise KeyError(f"unknown adapter {req.adapter_id!r}")
        if self._n_prefix:
            # reject bad vision HERE, not as an opaque TypeError mid-admission
            # (by which point the adapter would already be pinned)
            want = (self.cfg.num_vision_tokens, self.cfg.vision_dim)
            got = None if req.vision is None else np.shape(req.vision)
            if got != want:
                raise ValueError(
                    f"request {req.uid}: vision-prefix engine needs vision "
                    f"patches of shape {want}, got {got}")

    def submit(self, req: Request) -> int:
        self.validate(req)
        req.submitted_at = self.clock()
        req.admitted_at = None           # resubmittable: per-run fields
        req.first_token_at = None
        req.status = "ok"
        self.queue.append(req)
        return req.uid

    def _admit_pending(self) -> int:
        busy = self.busy_slots
        if not self.continuous and busy:
            return 0            # static batching: wait for the batch to drain
        admitted = 0
        newly: list[int] = []   # slots admitted this call (one prefill burst)
        free = [s for s in range(self.max_slots) if self._requests[s] is None]
        # a burst span only when there is actually admission work — an idle
        # engine step records nothing
        burst = (self.telemetry.span("admit_burst", cat="serving",
                                     queued=len(self.queue), free=len(free))
                 if self.queue and free else contextlib.nullcontext())
        burst.__enter__()
        while self.queue and free:
            req = self.queue[0]
            try:
                bank_slot = self.store.acquire(req.adapter_id)
            except AdapterQuarantinedError as e:
                # the adapter went bad between submit and admission: fail
                # THIS request (it never occupies a slot) and keep
                # admitting — a quarantined tenant must not stall the queue
                self.queue.popleft()
                self._fail_admission(req, str(e))
                continue
            except RuntimeError:
                break            # adapter bank exhausted by pinned tenants
            self.queue.popleft()
            slot = free.pop(0)
            n_p = len(req.prompt_tokens)
            ptoks = np.zeros((self.max_prompt,), np.int32)
            ptoks[:n_p] = np.asarray(req.prompt_tokens, np.int32)
            plen = self._n_prefix + n_p
            tlen = plen + req.gen_len - 1      # last fed position + 1
            vis = jnp.zeros((0,), jnp.float32)
            if self._n_prefix:
                vis = jnp.asarray(req.vision, jnp.float32)
            rng = jnp.zeros((2,), jnp.uint32)
            if self.sampling is not None:
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(self.sample_seed), req.uid)
            self.dispatch_count["serve_admit"] += 1
            with self.telemetry.span("serve_admit", cat="dispatch",
                                     uid=req.uid, slot=slot, slo=req.slo):
                self._state, self._cache = self._admit_fn(
                    self.params, self._state, self._cache,
                    jnp.asarray(slot, jnp.int32), jnp.asarray(ptoks), vis,
                    jnp.asarray(bank_slot, jnp.int32),
                    jnp.asarray(plen, jnp.int32),
                    jnp.asarray(tlen, jnp.int32), rng)
            # queue-wait is observed at RETIRE (ok completions only) so a
            # request admitted but later timed out cannot pollute the
            # histogram percentiles
            req.admitted_at = self.clock()
            self._requests[slot] = req
            self._pos_h[slot] = 0
            self._plen_h[slot] = plen
            self._tlen_h[slot] = tlen
            newly.append(slot)
            admitted += 1
        burst.__exit__(None, None, None)
        if self.prefill_chunk is not None and newly:
            # SHARED chunked prefill: one burst of max_s ⌈P_s/chunk⌉
            # dispatches fills EVERY slot admitted this step together (the
            # prefill program advances every prefill-phase slot, so
            # same-step admissions ride the same dispatches; a slot whose
            # shorter prompt finishes early just stops advancing).  Beats
            # the per-request Σ_s ⌈P_s/chunk⌉ whenever a step admits more
            # than one request — burst accounting is recorded in
            # ``prefill_bursts`` and asserted by bench --quick-prefill.
            fills = [int(self._plen_h[s]) - 1 for s in newly]
            n_disp = max(-(-f // self.prefill_chunk) for f in fills)
            self.prefill_bursts.append(
                {"fills": fills, "dispatches": n_disp})
            self._c_prefill_tokens.inc(sum(fills))
            self._c_prefill_rows.inc(
                n_disp * self.max_slots * self.prefill_chunk)
            with self.telemetry.span("prefill_burst", cat="serving",
                                     slots=len(newly), dispatches=n_disp):
                for _ in range(n_disp):
                    self.dispatch_count["serve_prefill"] += 1
                    with self.telemetry.span("serve_prefill",
                                             cat="dispatch"), \
                         warnings.catch_warnings():
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable")
                        self._state, self._cache = self._prefill_fn(
                            self.params, self.store.scan_stack, self._state,
                            self._cache)
            for s, n_fill in zip(newly, fills):
                self._pos_h[s] = n_fill
        return admitted

    def _fail_admission(self, req: Request, error: str) -> dict:
        """Complete ``req`` with an error status WITHOUT it ever occupying
        a slot (quarantined adapter discovered at admission time)."""
        req.status = "error"
        rec = {"uid": req.uid, "adapter_id": req.adapter_id,
               "slo": req.slo, "status": "error", "error": error,
               "attempts": req.attempts,
               "tokens": np.zeros((0,), np.int32),
               "latency_s": self.clock() - req.submitted_at}
        self._c_errors.inc()
        self._c_completed.inc()
        self.telemetry.instant("request_complete", cat="serving",
                               uid=req.uid, slo=req.slo, status="error")
        self.completed.append(rec)
        self._admit_failed.append(rec)
        return rec

    def _retire_finished(self) -> list[dict]:
        done = [s for s in self.busy_slots if self._pos_h[s] >= self._tlen_h[s]]
        if not done:
            return []
        self.dispatch_count["fetch"] += 1
        idx = np.asarray(done)
        with self.telemetry.span("fetch", cat="dispatch", rows=len(done)):
            # fault flags ride the SAME fetch — detection adds no sync
            gen_rows, fault_rows = jax.device_get(
                (self._state["gen"][idx], self._state["fault"][idx]))
        out = []
        now = self.clock()
        m = self.telemetry.metrics
        for i, s in enumerate(done):
            req = self._requests[s]
            self.store.release(req.adapter_id)
            self._requests[s] = None
            self._plen_h[s] = 0
            self._tlen_h[s] = 0
            status = "error" if bool(fault_rows[i]) else "ok"
            req.status = status
            rec = {"uid": req.uid, "adapter_id": req.adapter_id,
                   "slo": req.slo, "status": status,
                   "attempts": req.attempts,
                   "tokens": np.asarray(gen_rows[i][:req.gen_len]),
                   "latency_s": now - req.submitted_at,
                   "ttft_s": req.first_token_at - req.submitted_at,
                   "queue_wait_s": req.admitted_at - req.submitted_at}
            if req.deadline_at is not None:
                rec["deadline_s"] = req.deadline_at - req.submitted_at
            if req.degraded:
                rec["degraded"] = True
            if status == "error":
                rec["error"] = "non-finite logits during decode"
            out.append(rec)
            if status == "ok":
                # histograms see OK completions ONLY: faulted rows emit
                # garbage timings for garbage tokens and must not move
                # the percentiles the SLO report is built from
                self._h_latency.observe(rec["latency_s"])
                self._h_ttft.observe(rec["ttft_s"])
                self._h_queue_wait.observe(rec["queue_wait_s"])
                m.histogram(f"serving.latency_seconds.{req.slo}").observe(
                    rec["latency_s"])
                m.histogram(f"serving.ttft_seconds.{req.slo}").observe(
                    rec["ttft_s"])
                self._c_tokens.inc(req.gen_len)
            else:
                self._c_errors.inc()
            self._c_completed.inc()
            self.telemetry.instant("request_complete", cat="serving",
                                   uid=req.uid, slo=req.slo, status=status)
        self.completed.extend(out)
        return out

    # ------------------------------------------------------------ cancellation
    def cancel_slot(self, slot: int, *, status: str = "cancelled") -> dict:
        """Cancel the in-flight request in ``slot`` at a step boundary.
        Pure host bookkeeping — the adapter unpins, the host mirrors zero,
        and the slot rejoins the free pool for the next admission.  The
        device row keeps advancing inside the shared program until
        re-admission resets it (rows are independent; admission rewrites
        every slot buffer), so cancellation adds ZERO dispatches.  The
        record is returned, appended to ``completed``, and counted under
        ``serving.timeout`` / ``serving.cancelled`` — never under the
        latency/TTFT histograms."""
        req = self._requests[slot]
        if req is None:
            raise ValueError(f"slot {slot} has no in-flight request")
        self.store.release(req.adapter_id)
        self._requests[slot] = None
        self._pos_h[slot] = 0
        self._plen_h[slot] = 0
        self._tlen_h[slot] = 0
        req.status = status
        rec = {"uid": req.uid, "adapter_id": req.adapter_id,
               "slo": req.slo, "status": status, "attempts": req.attempts,
               "tokens": np.zeros((0,), np.int32),
               "latency_s": self.clock() - req.submitted_at}
        (self._c_timeout if status == "timeout" else self._c_cancelled).inc()
        self._c_completed.inc()
        self.telemetry.instant("request_cancelled", cat="serving",
                               uid=req.uid, slo=req.slo, status=status,
                               slot=slot)
        self.completed.append(rec)
        return rec

    def cancel(self, uid: int, *, status: str = "cancelled") -> dict:
        """Cancel a request by uid — queued (removed before it ever
        occupies a slot) or in-flight (via :meth:`cancel_slot`)."""
        for i, r in enumerate(self.queue):
            if r.uid == uid:
                del self.queue[i]
                r.status = status
                rec = {"uid": r.uid, "adapter_id": r.adapter_id,
                       "slo": r.slo, "status": status,
                       "attempts": r.attempts,
                       "tokens": np.zeros((0,), np.int32),
                       "latency_s": self.clock() - r.submitted_at}
                (self._c_timeout if status == "timeout"
                 else self._c_cancelled).inc()
                self._c_completed.inc()
                self.telemetry.instant("request_cancelled", cat="serving",
                                       uid=r.uid, slo=r.slo, status=status)
                self.completed.append(rec)
                return rec
        for s in self.busy_slots:
            if self._requests[s].uid == uid:
                return self.cancel_slot(s, status=status)
        raise KeyError(f"no queued or in-flight request with uid {uid}")

    # ------------------------------------------------------------ driving
    def step(self) -> list[dict]:
        """Admit → one fused decode dispatch → retire.  Returns the requests
        that completed this step (including admission-time quarantine
        failures, which complete without ever occupying a slot)."""
        self._admit_pending()
        failed, self._admit_failed = self._admit_failed, []
        busy = self.busy_slots
        if not busy:
            return failed
        self.dispatch_count["serve_step"] += 1
        self.steps += 1
        with self.telemetry.span("serve_step", cat="dispatch",
                                 slots=len(busy)), \
             warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            self._state, self._cache = self._step_fn(
                self.params, self.store.scan_stack, self._state, self._cache)
        now = self.clock()
        for s in busy:
            self._pos_h[s] += 1
            if self._pos_h[s] == self._plen_h[s]:
                # this step processed the last prompt position — it emitted
                # the request's first token (time-to-first-token, dispatch
                # clock: the token itself crosses to host only at retire)
                self._requests[s].first_token_at = now
        return failed + self._retire_finished()

    def run(self, requests=None, max_steps: int | None = None) -> list[dict]:
        """Submit ``requests`` (optional) and step until queue and slots are
        drained; returns the completion records in completion order.
        ``max_steps`` bounds THIS call (``self.steps`` is engine-lifetime)."""
        for r in requests or ():
            self.submit(r)
        n0 = len(self.completed)
        steps0 = self.steps
        while self.queue or self.busy_slots:
            self.step()
            if max_steps is not None and self.steps - steps0 >= max_steps:
                raise RuntimeError(f"exceeded max_steps={max_steps} with "
                                   f"{len(self.queue)} queued requests")
        return self.completed[n0:]

    def reset(self) -> None:
        """Return the engine to empty (no queued/busy requests, zeroed slot
        state, fresh counters) while KEEPING the compiled step/admit
        functions — benchmark reps and repeated workloads pay compilation
        once.  In-flight adapters are unpinned; the store's residency (hot
        set, LRU order) is deliberately left as-is."""
        for s in self.busy_slots:
            self.store.release(self._requests[s].adapter_id)
            self._requests[s] = None
        self.queue.clear()
        self.completed = []
        self._admit_failed = []
        self._state = jax.tree_util.tree_map(jnp.zeros_like, self._state)
        self._pos_h[:] = 0
        self._plen_h[:] = 0
        self._tlen_h[:] = 0
        self.steps = 0
        self.prefill_bursts = []
        self.dispatch_count.clear()
