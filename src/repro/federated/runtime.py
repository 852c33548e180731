"""Federated LoRA training runtime (server + clients + round loop).

One communication round (paper Fig. 3):

1. server distributes the global LoRA truncated to each sampled client's rank
   (``truncate_redistribute``);  FLoRA instead folds the accumulated dense
   delta into the effective base weights and clients re-init fresh LoRA;
2. each client runs ``local_steps`` LoRA-only AdamW steps on its private,
   possibly modality-incomplete shard (jit'd ``lax.scan`` over prefetched
   batches);
3. **LoRA editing** (FediLoRA Sec. 3.2) runs at the end of local fine-tuning
   and *before* aggregation: cosine-similarity vs. the previous round's
   global A, argmin layer, soft blend;
4. the server aggregates the sampled clients' padded adapters with the
   configured strategy (FedAvg / HetLoRA / FLoRA / FediLoRA), dispatched
   through ``repro.core.aggregation.AGGREGATORS``.

Clients keep their post-edit adapters for the *personalized* evaluation; the
aggregated adapter is the *global* evaluation target (paper Table 1).

Fused round engine
------------------

``run_round`` executes the whole round as ONE jit-compiled, buffer-donated
program (``repro.launch.fedround.make_round_engine``):

* client adapters live as persistently *stacked* device arrays
  ``[K, ...]`` (plus ``ranks[K]``) — sampled-client gather/scatter happens
  on device, never as per-client host pytrees;
* local AdamW training, HetLoRA self-pruning and layer-wise editing are
  vmapped over the client axis; aggregation dispatches through the shared
  registry (the ``fedilora_kernel`` entry lowers to the Pallas ``dim_agg``
  kernel on TPU);
* batches are gathered/stacked device-side from per-client device-resident
  shards; the only host synchronisation is one deferred metrics fetch per
  round (losses + edited layers + post-pruning ranks);
* the stacked state is donated into the step, and the input global adapter
  is snapshotted through the program as the next ``prev_global`` — donation
  therefore cannot invalidate it (the use-after-donate hazard the old
  ``prev_global = global_lora`` aliasing would have caused).

Paged population (``FederatedConfig.paged``)
--------------------------------------------

With ``paged=True`` the persistent ``[K, ...]`` stacks are replaced by a
host-backed ``repro.federated.client_store.ClientStateStore``: the device
holds only a cohort-sized bank of client rows (adapters, ranks, sizes,
corpus shards), cohorts page in through LRU slot assignment with
write-back-on-evict, and the SAME fused engine dispatches over the bank
with ``idx`` = bank slots — still ONE jitted ``round_step`` per round, and
bit-identical to the resident path because every per-client computation is
row-local.  Page-in scatters and eviction captures are enqueued on the
device stream *behind* the in-flight round (they consume its output bank
references), so prefetch and write-back cost no host synchronisation; under
``run_round_pipelined`` they overlap the previous round's execution.
``run_round_async`` keeps each in-flight cohort pinned until retirement.
Device residency is O(cohort), host residency O(K) (optionally LRU-spilled
to disk via ``store_host_slots``/``store_spill_dir``) — the unlock for
populations of 10^5+ clients (see ``benchmarks/bench_fedround.py
--population``).

``run_round_reference`` preserves the host-driven per-client loop — the
numerical reference for the fused path and the sequential baseline measured
by ``benchmarks/bench_fedround.py``.  Evaluation decode
(``generation_scores``) is KV-cached O(T) via
``repro.launch.steps.make_greedy_generate``; pass ``cached=False`` for the
O(T²) full-re-forward-per-token reference.

Async pipeline (execution model)
--------------------------------

``run_round`` is synchronous at the *timeline* level: it dispatches round t
and immediately blocks on that round's deferred metrics fetch, so the host
work of round t+1 (client sampling, per-client batch-index builds, dispatch)
only starts after the device finishes round t.  Two async drivers remove
that barrier:

* ``run_round_pipelined`` — double-buffers the engine.  Each call performs
  round t+1's host-side sampling + batch-index build while round t still
  executes on device, fetches round t's metrics (blocking only on t, whose
  execution the host work just overlapped — never on the round about to be
  dispatched), then *enqueues* round t+1 (JAX dispatch is asynchronous).
  WHAT IS OVERLAPPED: host sampling/index-build of round t+1 with
  device execution of round t.  WHAT IS ONE ROUND STALE: everything the
  host reads — the returned record (losses, edited layers) and the
  ``client_ranks`` host mirror describe round t when round t+1 is already
  in flight; the first call returns ``None``.  Device-side state
  (``stacked_lora``, ``global_lora``, ``ranks``) is always current — only
  *fetches* lag, never the computation.  ``flush_rounds()`` drains the last
  pending fetch (call it before reading final metrics or mixing drivers;
  ``run_round`` auto-flushes).
* ``run_round_async`` — buffered asynchronous FL (FedBuff-style) on top of
  the same stacked state: each tick dispatches a ``client_update_step``
  cohort against the *current* global (no aggregation), retires cohorts
  whose simulated delay (``FederatedConfig.async_delays``) has elapsed into
  a device-resident buffer of per-client deltas, and merges exactly
  ``buffer_size`` (M) deltas through the ``fedbuff`` registry entry whenever
  the buffer fills — slow clients never stall fast ones; their late deltas
  arrive with staleness = (server versions elapsed) and are discounted
  ``(1+s)^-staleness_decay``, with the forfeited weight mass staying on the
  current global.  With zero delays and ``M = n_sample`` every tick is
  dispatch → retire → merge and the timeline is *exactly* the synchronous
  ``fedilora`` round (tested).

``dispatch_count`` (a ``collections.Counter``) tallies every jitted dispatch
by name (``round_step``, ``client_update``, ``buffer_merge``,
``population_eval``, ``eval_loss``, ``generate``) — the benchmark's
``--quick`` mode and the tier-2 smoke test assert on it to catch dispatch-
count regressions without timing flakiness.

``evaluate_personalized`` runs the whole K-client sweep as ONE jitted
dispatch by default (``vmapped=True``): eval loss and the KV-cached greedy
decode are vmapped over the stacked ``[K, ...]`` adapter state
(``repro.launch.steps.make_population_eval``), replacing the ~2K-dispatch
per-client host loop (kept as ``vmapped=False`` — the reference and the
benchmark baseline).
"""

from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as AG
from repro.core.editing import edit_lora
from repro.core.lora import (LoRAConfig, init_lora_params, mask_lora_params,
                             truncate_redistribute)
from repro.data.synthetic import EOS
from repro.federated.config import FederatedConfig
from repro.federated.faults import FaultSchedule
from repro.launch.fedround import (apply_weight_deltas,
                                   make_buffer_merge_step,
                                   make_client_update_step, make_round_engine)
from repro.launch.steps import make_greedy_generate, make_population_eval
from repro.metrics import corpus_scores
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import OptimizerConfig, make_optimizer
from repro.telemetry import Telemetry

Pytree = Any

# batch keys that ride the training step (everything else, e.g. raw concept
# ids, stays on the host)
_BATCH_KEYS = ("tokens", "labels", "loss_mask", "image", "image_mask",
               "audio", "text_mask")

# keys an evaluation batch may carry (loss + generation)
_EVAL_KEYS = ("tokens", "labels", "loss_mask", "image", "audio")


def _mask_decode_bounds(loss_mask: np.ndarray) -> tuple[int, int]:
    """Derive the shared greedy-decode window (``cap_start``, ``gen_len``)
    from a supervised-position mask, asserting the mask is uniform across
    rows.  The decode compiles ONE static window for the whole batch; a
    non-uniform mask (rows whose caption starts elsewhere) would silently
    generate at the wrong positions, so fail loudly instead."""
    lm = np.asarray(loss_mask) > 0
    if lm.ndim != 2:
        raise ValueError(f"loss_mask must be [rows, seq], got {lm.shape}")
    if not (lm == lm[0]).all():
        bad = int(np.argmax((lm != lm[0]).any(axis=1)))
        raise ValueError(
            "loss_mask is not uniform across rows (first mismatch at row "
            f"{bad}): greedy decode derives one static (cap_start, gen_len) "
            "window from row 0 and would silently mis-decode rows with a "
            "different supervised span.  Evaluate such corpora per-row or "
            "regenerate them with a shared caption position (the synthetic "
            "corpora are uniform by construction).")
    cap_start = int(np.argmax(lm[0]))
    gen_len = int(lm[0].sum())
    if gen_len == 0:
        raise ValueError(
            "loss_mask has no supervised positions (all-zero rows): there "
            "is no caption window to decode — greedy generation over such a "
            "corpus would silently emit one bogus token at position 0.")
    return cap_start, gen_len


def _score_generated(gen: np.ndarray, labels: np.ndarray,
                     loss_mask: np.ndarray) -> dict:
    """Token-id generations → Google-BLEU / ROUGE-LSum (EOS-truncated)."""
    hyps, refs = [], []
    for i in range(gen.shape[0]):
        h = np.asarray(gen)[i].tolist()
        r = np.asarray(labels)[i][np.asarray(loss_mask)[i] > 0].tolist()
        h = h[: h.index(EOS)] if EOS in h else h
        r = [x for x in r if x != EOS]
        hyps.append(h)
        refs.append(r)
    return corpus_scores(hyps, refs)


@dataclasses.dataclass
class ServerState:
    global_lora: Pytree          # padded to r_g
    prev_global: Pytree          # A_{g,t-1} for editing (paper Eq. 6)
    round: int = 0
    flora_delta: Pytree | None = None


class ClientState:
    """One client's private data plus a *view* of its slice of the trainer's
    stacked device state — ``lora``/``rank`` read through to
    ``trainer.stacked_lora[k]`` / ``trainer.client_ranks[k]`` so the
    persistent representation stays a single ``[K, ...]`` array."""

    def __init__(self, trainer: "FederatedTrainer", index: int, data: dict,
                 eval_data: dict, size: int, rng: np.random.Generator):
        self._trainer = trainer
        self._index = index
        self.data = data
        self.eval_data = eval_data
        self.size = size
        self.rng = rng

    @property
    def rank(self) -> int:
        return int(self._trainer.client_ranks[self._index])

    @property
    def lora(self) -> Pytree:
        k = self._index
        tr = self._trainer
        if tr.fcfg.paged:
            return jax.tree_util.tree_map(jnp.asarray, tr.store.client_lora(k))
        return jax.tree_util.tree_map(lambda x: x[k], tr.stacked_lora)


class FederatedTrainer:
    def __init__(self, model_cfg: ModelConfig, fed_cfg: FederatedConfig,
                 opt_cfg: OptimizerConfig, client_train: list[dict],
                 client_eval: list[dict], global_test: dict,
                 base_params: Pytree | None = None, seed: int = 0,
                 client_mesh: "jax.sharding.Mesh | None" = None,
                 mesh: "jax.sharding.Mesh | None" = None,
                 telemetry: Telemetry | None = None):
        """``mesh``: optional device mesh the round engines run over —
        either 1-D (any axis name; sampled clients split over it, exactly
        the old ``client_mesh`` behaviour, bit-identical) or 2-D with axes
        ``(client, "model")``: clients split over the first axis while each
        client group's local training runs tensor-parallel over ``"model"``
        (frozen base weights placed by ``sharding.param_spec``, LoRA state
        replicated per group — see ``repro.launch.fedround``).  The
        persistent stacked ``[K, ...]`` state and the device-resident
        corpus are placed with ``NamedSharding``s up front on first use.
        ``client_mesh`` is the legacy alias for the same argument.
        ``None`` = single device."""
        if mesh is not None and client_mesh is not None:
            raise ValueError("pass either mesh= or client_mesh=, not both")
        self.mcfg = model_cfg
        self.fcfg = fed_cfg
        self.ocfg = opt_cfg
        self.client_mesh = mesh if mesh is not None else client_mesh
        self._mesh_placed = None       # mesh the state was last placed for
        self.global_test = global_test
        key = jax.random.PRNGKey(seed)
        self.base_params = base_params if base_params is not None \
            else T.init_params(key, model_cfg)
        self.specs = T.lora_specs(model_cfg)
        r_g = fed_cfg.global_rank
        self.lcfg = LoRAConfig(rank=r_g, alpha=fed_cfg.lora_alpha)
        self.lora_scale = fed_cfg.lora_alpha / r_g
        g0 = init_lora_params(jax.random.fold_in(key, 1), self.specs, self.lcfg)
        self.server = ServerState(global_lora=g0,
                                  prev_global=jax.tree_util.tree_map(jnp.copy, g0))
        # every jitted dispatch is tallied here by name — the benchmark's
        # --quick modes and the tier-2 smoke test assert on these counts.
        # The counter lives in the telemetry registry (counter_group keeps
        # it a real collections.Counter, so all existing call sites and
        # asserts are untouched); a trainer built without telemetry= gets a
        # private disabled bundle — spans no-op, the counter still counts
        self.telemetry = (telemetry if telemetry is not None
                          else Telemetry(enabled=False))
        self.dispatch_count: collections.Counter = \
            self.telemetry.metrics.counter_group("fed.dispatch")
        self.client_ranks = np.asarray(fed_cfg.ranks, np.int32)   # host mirror
        sizes = np.asarray([d["tokens"].shape[0] for d in client_train],
                           np.float32)
        self.clients: list[ClientState] = []
        for k in range(fed_cfg.num_clients):
            self.clients.append(ClientState(
                self, k, data=client_train[k], eval_data=client_eval[k],
                size=int(sizes[k]),
                rng=np.random.default_rng(seed + 7 * k + 1)))
        keys = [kk for kk in _BATCH_KEYS
                if all(kk in d for d in client_train)]
        partial = [kk for kk in _BATCH_KEYS
                   if kk not in keys and any(kk in d for d in client_train)]
        if partial:
            raise ValueError(
                f"batch keys {partial} present in only some client shards; "
                "the stacked corpus needs uniform keys (add the key — e.g. an "
                "all-ones mask — to every client or drop it everywhere)")
        # per-client initial adapter (deterministic PRNG fold — shared by
        # the eager resident stack, the store's lazy materialisation, and
        # checkpoint restores of never-materialised paged clients)
        self._init_lora_fn = lambda k: init_lora_params(
            jax.random.fold_in(key, 100 + k), self.specs, self.lcfg,
            client_rank=fed_cfg.ranks[k])
        if fed_cfg.paged:
            # ---- host-backed population, cohort-sized device bank --------
            if self.client_mesh is not None:
                raise NotImplementedError(
                    "paged=True with a round mesh is not supported yet — "
                    "page the population or shard the cohort, not both")
            from repro.federated.client_store import ClientStateStore

            slots = fed_cfg.store_slots or self._n_sample
            if slots < self._n_sample:
                raise ValueError(
                    f"store_slots={slots} is smaller than the sampled "
                    f"cohort ({self._n_sample}); the bank must hold at "
                    "least one whole cohort")
            # lazy per-client adapter init with the SAME per-client PRNG
            # fold the resident path stacks eagerly — paged state is
            # therefore bit-identical, and K=10^5 costs nothing up front
            self.store = ClientStateStore(
                num_clients=fed_cfg.num_clients, slots=slots,
                init_fn=self._init_lora_fn,
                ranks=self.client_ranks, sizes=sizes,
                data=client_train, batch_keys=keys,
                dispatch_count=self.dispatch_count,
                host_slots=fed_cfg.store_host_slots,
                spill_dir=fed_cfg.store_spill_dir,
                telemetry=self.telemetry)
            self.stacked_lora = None
            self._stacked_data = None
            self._ranks_dev = None
            self._sizes_dev = None
        else:
            # ---- persistent stacked client state [K, ...] ----------------
            self.store = None
            loras = [self._init_lora_fn(k)
                     for k in range(fed_cfg.num_clients)]
            self.stacked_lora: Pytree = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *loras)
            self._ranks_dev = jnp.asarray(self.client_ranks)
            self._sizes_dev = jnp.asarray(sizes)
            # device-resident training corpus [K, N_max, ...] (zero-padded
            # to the longest shard; batch indices never reach the padding)
            # — the fused round gathers its minibatches from this in-program
            n_max = max(d["tokens"].shape[0] for d in client_train)
            self._stacked_data = {
                kk: jnp.stack([
                    np.pad(np.asarray(d[kk]),
                           [(0, n_max - d[kk].shape[0])]
                           + [(0, 0)] * (np.asarray(d[kk]).ndim - 1))
                    for d in client_train])
                for kk in keys}
        self._opt_init, self._opt_update = make_optimizer(opt_cfg)
        self._round_step = None        # fused engine, built on first round
        self._local_train = None       # reference per-client jit, lazy
        self._gen_cache: dict = {}     # jitted cached-decode fns per shape
        self._pop_eval_cache: dict = {}  # jitted population sweeps per shape
        self._eval_loss = jax.jit(self._eval_loss_impl)
        self._next_logits = jax.jit(self._next_logits_impl)
        self.rng = np.random.default_rng(seed)
        self.history: list[dict] = []
        # ---- pipelined rounds: the in-flight (round, sampled, out, slots)
        # whose metrics have not been fetched yet (one round of lag by design)
        self._pending: tuple | None = None
        self._last_slots = None        # bank slots of the last paged cohort
        # ---- buffered async (fedbuff) state ------------------------------
        self._client_update_step = None
        self._merge_step = None
        self._inflight: list[dict] = []   # dispatched cohorts not yet retired
        self._buffer: list[dict] = []     # retired per-client deltas (device)
        self._async_tick = 0
        self._global_version = 0          # server merges applied so far
        # measured per-client wall-clock local-training time (EMA, seconds);
        # recorded when fcfg.measure_delays and consumed by run_round_async
        self.client_step_ema = np.zeros((fed_cfg.num_clients,), np.float64)
        self._ema_seen = np.zeros((fed_cfg.num_clients,), bool)
        # driver paths whose jitted fn has already run once — the FIRST
        # measurement of a path includes trace+compile (seconds vs ms) and
        # would poison the EMA with an enormous bogus delay, so discard it
        self._measure_warm: set = set()
        # ---- fault injection (robustness) --------------------------------
        # stateless per-(round, client) schedule: identical draws under
        # paged/resident state and across checkpoint restores (the "RNG
        # position" is the round/tick counter the checkpoint already holds)
        self.fault_schedule = (FaultSchedule(fed_cfg.faults,
                                             fed_cfg.num_clients)
                               if fed_cfg.faults.active else None)
        # cumulative health counters (n_dropped / n_forfeited / n_deferred /
        # n_corrupted / n_nonfinite / clip_rate_sum / fault_rounds) — per-
        # round values ride the existing single metrics fetch; like
        # dispatch_count, a real Counter adopted by the registry
        self.health: collections.Counter = \
            self.telemetry.metrics.counter_group("fed.health")
        # round/step latency distributions and cheap callback gauges — all
        # host-side reads of state the trainer keeps anyway
        m = self.telemetry.metrics
        self._h_round = m.histogram("fed.round_seconds")
        self._h_client_step = m.histogram("fed.client_step_seconds")
        m.gauge_fn("fed.server_round", lambda: float(len(self.history)))
        m.gauge_fn("fed.async_buffer_fill",
                   lambda: float(len(self._buffer)))
        m.gauge_fn("fed.async_inflight", lambda: float(len(self._inflight)))
        m.gauge_fn("fed.client_step_ema_mean",
                   lambda: float(self.client_step_ema[self._ema_seen].mean())
                   if self._ema_seen.any() else 0.0)

    # ------------------------------------------------------------------ local
    def _local_train_impl(self, base_params, lora, rank, batches):
        """scan over prefetched batches; grads masked to the client's rank
        subspace so padded dims stay exactly zero."""
        opt_state = self._opt_init(lora)
        r_g = self.lcfg.rank

        def loss_of(lo, mb):
            loss, _ = T.loss_fn(self.mcfg, base_params, lo, mb, self.lora_scale)
            return loss

        def step(carry, mb):
            lo, opt = carry
            loss, g = jax.value_and_grad(loss_of)(lo, mb)
            g = mask_lora_params(g, rank, r_g)
            lo, opt = self._opt_update(lo, g, opt)
            lo = mask_lora_params(lo, rank, r_g)
            return (lo, opt), loss

        (lora, _), losses = jax.lax.scan(step, (lora, opt_state), batches)
        return lora, losses

    def _batch_indices(self, client: ClientState) -> np.ndarray:
        """[local_steps, batch_size] example indices, drawn exactly like
        ``batch_iterator`` (shuffled epochs from the client's PRNG) — shared
        by the fused and reference paths so both see identical batches."""
        B, steps = self.fcfg.batch_size, self.fcfg.local_steps
        n = client.data["tokens"].shape[0]
        if n < B:
            raise ValueError(
                f"client shard has {n} examples < batch_size {B}; "
                "an epoch yields no batches")
        out: list[np.ndarray] = []
        while len(out) < steps:
            perm = client.rng.permutation(n)
            for i in range(0, n - B + 1, B):
                out.append(perm[i: i + B])
                if len(out) == steps:
                    break
        return np.stack(out)

    def _prefetch(self, client: ClientState) -> dict:
        """Reference-path prefetch: host-side gather of the same batch
        indices the fused path uses, one transfer per key — fused and
        reference engines train on identical batches by construction."""
        ix = self._batch_indices(client)
        return {k: jnp.asarray(v[ix]) for k, v in client.data.items()
                if k in _BATCH_KEYS}

    def _record_step_time(self, clients, seconds: float, *,
                          path: str | None = None,
                          only_unseen: bool = False) -> None:
        """Fold one wall-clock local-training measurement into the per-client
        EMA.  The reference loop measures each client individually; the
        fused/async cohort dispatch can only observe the cohort's wall clock
        — a uniform value that would ERASE individually measured
        heterogeneity if folded into every member, so the cohort path passes
        ``only_unseen=True`` and seeds unmeasured clients without touching
        measured ones.  ``path`` names the jitted fn being timed — its first
        invocation (compile-inclusive) is discarded."""
        if path is not None and path not in self._measure_warm:
            self._measure_warm.add(path)
            return
        self._h_client_step.observe(seconds)
        beta = self.fcfg.delay_ema_beta
        for k in np.atleast_1d(np.asarray(clients, np.int64)):
            if self._ema_seen[k]:
                if only_unseen:
                    continue
                self.client_step_ema[k] = (beta * self.client_step_ema[k]
                                           + (1.0 - beta) * seconds)
            else:
                self.client_step_ema[k] = seconds
                self._ema_seen[k] = True

    def derived_async_delays(self) -> tuple:
        """Async delays (rounds-to-finish) derived from the measured EMAs:
        a client whose step time is n× the fastest measured client retires
        n-1 ticks late.  Unmeasured clients mixed into a measured pool get
        the POOL MEDIAN's delay rather than a silent 0 — a fresh client is
        far more likely to behave like the typical measured one than like
        the fastest (no measurements at all still means all-zero delays)."""
        if not self._ema_seen.any():
            return (0,) * self.fcfg.num_clients
        base = float(self.client_step_ema[self._ema_seen].min())
        delays = np.zeros((self.fcfg.num_clients,), np.int64)
        if base > 0:
            ratio = self.client_step_ema[self._ema_seen] / base
            delays[self._ema_seen] = np.maximum(
                np.round(ratio).astype(np.int64) - 1, 0)
            med = float(np.median(self.client_step_ema[self._ema_seen]))
            delays[~self._ema_seen] = max(int(round(med / base)) - 1, 0)
        return tuple(int(d) for d in delays)

    @property
    def _n_sample(self) -> int:
        """Clients per round — also the jitted engine's static client-axis
        size, so host sampling and the compiled program must agree."""
        fc = self.fcfg
        return max(int(round(fc.sample_rate * fc.num_clients)), 1)

    def _sample_clients(self, pool: list | None = None,
                        round_idx: int | None = None) -> list[int]:
        """Sample one cohort.  ``pool`` restricts the draw (run_round_async
        passes the idle clients).  ``sampling="availability"`` down-weights
        slow clients by their measured local-step EMA —
        ``w_k ∝ (fastest_ema / ema_k)^alpha`` for measured clients, 1.0 for
        unmeasured ones — and falls back to uniform until any EMA lands, so
        the default configuration's RNG stream is untouched.  With an active
        fault schedule, availability sampling additionally routes around the
        clients drawn offline for ``round_idx`` (the server knows who is
        unreachable) — unless that would leave fewer than a cohort."""
        fc = self.fcfg
        if fc.sampling not in ("uniform", "availability"):
            raise ValueError(
                f"unknown sampling {fc.sampling!r} "
                "(expected 'uniform' or 'availability')")
        n = self._n_sample
        if (fc.sampling == "availability"
                and self.fault_schedule is not None):
            off = self.fault_schedule.offline(
                self.server.round if round_idx is None else round_idx)
            if off:
                src = range(fc.num_clients) if pool is None else pool
                kept = [int(k) for k in src if int(k) not in off]
                if len(kept) >= n:
                    pool = kept
        ids = None if pool is None else np.asarray(pool, np.int64)
        if fc.sampling == "availability":
            seen = self._ema_seen if ids is None else self._ema_seen[ids]
            if seen.any():
                ema = (self.client_step_ema if ids is None
                       else self.client_step_ema[ids])
                w = np.ones(seen.shape[0], np.float64)
                base = float(ema[seen].min())
                if base > 0:
                    w[seen] = (base / ema[seen]) ** fc.availability_alpha
                src = np.arange(fc.num_clients) if ids is None else ids
                return sorted(int(k) for k in self.rng.choice(
                    src, n, replace=False, p=w / w.sum()))
        if ids is None:
            # keep the historical call shape — bit-identical RNG stream
            return sorted(self.rng.choice(fc.num_clients, n, replace=False))
        return sorted(self.rng.choice(ids, n, replace=False))

    # ------------------------------------------------------------------ mesh
    @property
    def client_mesh(self):
        return self._client_mesh

    @client_mesh.setter
    def client_mesh(self, m):
        """Reassigning the mesh invalidates the compiled round engines —
        their shard_map mesh / sharding constraints and cohort padding are
        baked in at build time, so a stale engine would crash on (or
        silently ignore) operands re-placed for the new mesh."""
        if m is not None and getattr(self, "fcfg", None) is not None \
                and self.fcfg.paged:
            raise NotImplementedError(
                "paged=True with a round mesh is not supported yet — "
                "page the population or shard the cohort, not both")
        if getattr(self, "_client_mesh", None) is not m:
            self._round_step = None
            self._client_update_step = None
            if getattr(self, "_pop_eval_cache", None):
                self._pop_eval_cache = {}
        self._client_mesh = m

    @property
    def mesh(self):
        """The configured round mesh (alias of ``client_mesh``)."""
        return self.client_mesh

    @mesh.setter
    def mesh(self, m):
        self.client_mesh = m

    def _place_mesh_state(self) -> None:
        """Place the persistent device state with ``NamedSharding``s for the
        configured mesh (idempotent; re-runs when the mesh changes):

        * stacked client adapters + device-resident corpus: ``[K, ...]``
          row axis over the client axis (replicated when K doesn't divide);
        * frozen base params: ``sharding.param_spec`` — tensor-parallel
          over ``"model"`` on a 2-D mesh, degrading to replication on a
          1-D client mesh (no ``model``/``data`` axes to shard over); a
          vocabulary the model axis does not divide is padded
          (``sharding.pad_vocab``) so that the embedding and the loss
          split over it too;
        * global/prev adapters, ranks, sizes: replicated (aggregation
          objects).

        Placement up front means no per-round resharding: the jitted round
        consumes every operand where the shard_map/GSPMD partitioning
        expects it."""
        m = self.client_mesh
        if m is None or self._mesh_placed is m:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro import sharding as SH
        client_ax, model_ax = SH.round_mesh_axes(m)
        row = P(client_ax) if (self.fcfg.num_clients
                               % m.shape[client_ax] == 0) else P()
        rows = NamedSharding(m, row)
        self.stacked_lora = jax.device_put(self.stacked_lora, rows)
        self._stacked_data = jax.device_put(self._stacked_data, rows)
        rep = SH.replicated(m)
        self._ranks_dev = jax.device_put(self._ranks_dev, rep)
        self._sizes_dev = jax.device_put(self._sizes_dev, rep)
        self.server.global_lora = jax.device_put(self.server.global_lora, rep)
        self.server.prev_global = jax.device_put(self.server.prev_global, rep)
        # TP-only placement: the round mesh's first axis is the CLIENT
        # axis whatever its name — FSDP'ing the frozen base over it would
        # all-gather the weights per use
        if model_ax is not None:
            self.base_params = SH.pad_vocab(self.base_params,
                                            m.shape[model_ax])
        self.base_params = jax.device_put(
            self.base_params,
            SH.tree_param_shardings(self.base_params, m,
                                    spec_fn=SH.param_spec_tp))
        self._mesh_placed = m

    # ------------------------------------------------------------------ round
    def _get_round_step(self):
        self._place_mesh_state()
        if self._round_step is None:
            fc = self.fcfg
            step = make_round_engine(
                self.mcfg, self.ocfg, specs=self.specs,
                lora_scale=self.lora_scale, r_g=self.lcfg.rank,
                edit=fc.edit, aggregator=fc.aggregator,
                hetlora_beta=fc.hetlora_beta,
                hetlora_prune_gamma=fc.hetlora_prune_gamma,
                mesh=self.client_mesh, n_sample=self._n_sample,
                clip=fc.clip_norm or None, trim=fc.trim_frac,
                faults=self.fault_schedule is not None)
            # donate the persistent stacked state (in-place update on TPU);
            # base params too for FLoRA, which folds deltas into them
            donate = (1, 2, 3, 4) + ((0,) if fc.aggregator == "flora" else ())
            self._round_step = jax.jit(step, donate_argnums=donate)
        return self._round_step

    def _dispatch(self, name: str, fn, *args):
        """Invoke a jitted callable, tallying it in ``dispatch_count`` and
        spanning the host enqueue (the span name IS the dispatch-count key —
        bench --quick-telemetry asserts the two tallies agree).  Dispatch is
        asynchronous, so the span measures enqueue, not device time; no
        sync is added."""
        self.dispatch_count[name] += 1
        with self.telemetry.span(name, cat="dispatch"):
            return fn(*args)

    def _fault_cohort(self, round_idx: int, sampled: list[int]) -> dict:
        """Draw one cohort's fault operands from the schedule, feeding the
        measured step-time EMAs into the deadline check (unmeasured clients
        carry NaN — the schedule ignores them) and accumulating the host-
        side corruption count (corruption is invisible to the device-side
        health guards unless it produces non-finite values)."""
        with self.telemetry.span("fault_draw", cat="fed",
                                 round=round_idx, cohort=len(sampled)):
            ema = np.where(self._ema_seen, self.client_step_ema, np.nan)
            co = self.fault_schedule.cohort(round_idx, sampled, step_ema=ema)
            self.health["n_corrupted"] += int(co["n_corrupted"])
            return co

    def _build_round_inputs(self) -> tuple[list[int], np.ndarray]:
        """Host-side client sampling + per-client batch-index build — pure
        host work, free to overlap the device execution of an in-flight
        round."""
        with self.telemetry.span("sample_cohort", cat="fed"):
            sampled = self._sample_clients()
        with self.telemetry.span("build_batch_indices", cat="fed",
                                 cohort=len(sampled)):
            batch_idx = np.stack([self._batch_indices(self.clients[k])
                                  for k in sampled])
        return sampled, batch_idx

    def _enqueue_round(self, sampled: list[int],
                       batch_idx: np.ndarray) -> dict:
        """ENQUEUE the fused round dispatch (no host sync — JAX dispatch is
        async) and swap device state references to the new (in-flight)
        buffers.  Paged mode pages the cohort into the store's bank and
        dispatches the SAME engine over bank operands with ``idx`` = bank
        slots (``cids`` always carries the global ids — flora's fresh-init
        PRNG folds them, never slots)."""
        paged = self.fcfg.paged
        # first: on a mesh this places the state read below, so round 1 sees
        # the same operand shardings as every later round (one compile)
        step = self._get_round_step()
        cids = jnp.asarray(sampled, jnp.int32)
        if paged:
            slots = self.store.acquire_cohort(sampled)
            idx = jnp.asarray(slots, jnp.int32)
            lora, ranks, sizes, data = (
                self.store.lora_bank, self.store.ranks_bank,
                self.store.sizes_bank, self.store.data_bank)
        else:
            slots = None
            idx = cids
            lora, ranks, sizes, data = (self.stacked_lora, self._ranks_dev,
                                        self._sizes_dev, self._stacked_data)
        fault_args: tuple = ()
        if self.fault_schedule is not None:
            co = self._fault_cohort(self.server.round, sampled)
            fault_args = ({k: jnp.asarray(co[k])
                           for k in ("keep", "weight", "scale", "nan")},)
        with warnings.catch_warnings():
            # donation is a no-op off TPU/GPU; silence only this dispatch
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            out = self._dispatch(
                "round_step", step, self.base_params, lora, self.server.global_lora,
                self.server.prev_global, ranks, sizes, data, idx, cids,
                jnp.asarray(batch_idx, jnp.int32),
                jnp.asarray(self.server.round, jnp.int32), *fault_args)
        if paged:
            # adopt the in-flight output banks (donation consumed the old
            # refs), mark the cohort rows dirty for eviction write-back,
            # and unpin — the NEXT round's page-in scatters enqueue behind
            # this round in the device stream, so no host sync is needed
            self.store.adopt(out["stacked_lora"], out["ranks"])
            self.store.mark_trained(sampled)
            self.store.release_cohort(sampled)
        else:
            self.stacked_lora = out["stacked_lora"]
            self._ranks_dev = out["ranks"]
        self.server.prev_global = out["prev_global"]
        self.server.global_lora = out["global_lora"]
        if "base_params" in out:           # flora folded deltas into base
            self.base_params = out["base_params"]
        self.server.round += 1
        self._last_slots = slots
        return out

    def _fetch_round_record(self, round_no: int, sampled: list[int],
                            out: dict, slots=None) -> dict:
        """The one blocking host sync per round: metrics + post-prune ranks.
        ``slots`` (paged mode) maps the fetched bank-shaped ``ranks[S]``
        back onto the sampled clients' entries of the host mirror."""
        fetch = {"metrics": out["metrics"], "ranks": out["ranks"]}
        if "health" in out:        # faults active: health rides the SAME sync
            fetch["health"] = out["health"]
        with self.telemetry.span("metrics_fetch", cat="fed", round=round_no):
            fetched = jax.device_get(fetch)
        if slots is None:
            self.client_ranks = np.asarray(fetched["ranks"])
        else:
            # in-place: the store shares this array as its rank tier
            self.client_ranks[np.asarray(sampled, np.int64)] = \
                np.asarray(fetched["ranks"])[np.asarray(slots, np.int64)]
        edited = fetched["metrics"].get("edited")
        rec = {"round": round_no, "sampled": list(map(int, sampled)),
               "train_loss": float(np.mean(fetched["metrics"]["last_loss"])),
               "edited_layers": [] if edited is None
               else [int(e) for e in edited]}
        if "health" in fetched:
            h = {k: float(v) for k, v in fetched["health"].items()}
            rec["health"] = h
            for k in ("n_dropped", "n_forfeited", "n_nonfinite"):
                self.health[k] += int(h[k])
            self.health["clip_rate_sum"] += h["clip_rate"]
            self.health["fault_rounds"] += 1
        self.history.append(rec)
        return rec

    def run_round(self) -> dict:
        """One communication round = ONE fused jit dispatch (see module
        docstring).  Exactly one host sync: the deferred metrics fetch."""
        t0 = time.perf_counter()
        with self.telemetry.span("round", cat="fed",
                                 round=self.server.round):
            self.flush_rounds()            # drain any pipelined round first
            sampled, batch_idx = self._build_round_inputs()
            out = self._enqueue_round(sampled, batch_idx)
            rec = self._fetch_round_record(self.server.round, sampled, out,
                                           self._last_slots)
        self._h_round.observe(time.perf_counter() - t0)
        return rec

    def run_round_pipelined(self) -> dict | None:
        """Pipelined round: build round t's host inputs (sampling + batch
        indices — this is the work that overlaps round t-1's device
        execution), drain round t-1's metrics fetch, then enqueue round t.
        The returned record is one round stale by design (``None`` on the
        first call; ``flush_rounds()`` drains the last one).  The fetch
        never blocks on the round dispatched in the same call — only on the
        previous one, which the host work just overlapped.  See the module
        docstring."""
        t0 = time.perf_counter()
        with self.telemetry.span("round_pipelined", cat="fed",
                                 round=self.server.round):
            sampled, batch_idx = self._build_round_inputs()
            rec = self.flush_rounds()
            out = self._enqueue_round(sampled, batch_idx)
            self._pending = (self.server.round, sampled, out,
                             self._last_slots)
        self._h_round.observe(time.perf_counter() - t0)
        return rec

    def flush_rounds(self) -> dict | None:
        """Drain the pending pipelined metrics fetch (no-op when none)."""
        rec = None
        if self._pending is not None:
            rec = self._fetch_round_record(*self._pending)
            self._pending = None
        return rec

    # ------------------------------------------------------------- serving
    def export_adapters(self) -> dict:
        """Personalized adapters for serving registration:
        ``{"client<k>": (host lora pytree padded to r_g, true rank r_k)}``.
        One device fetch for the whole stacked state; the zero-rank-padding
        invariant makes the padded trees directly servable (see
        ``repro.serving.AdapterStore``).  Drains a pending pipelined round
        first so the exported adapters are the latest ones.  Paged mode
        streams per-client from the host tier (one bank flush, then zero
        device traffic — never materialises a ``[K, ...]`` stack)."""
        self.flush_rounds()
        if self.fcfg.paged:
            self.store.flush()
            return {f"client{k}": (self.store.host_adapter(k),
                                   int(self.client_ranks[k]))
                    for k in range(self.fcfg.num_clients)}
        host = jax.device_get(self.stacked_lora)
        return {
            f"client{k}": (jax.tree_util.tree_map(lambda x, k=k: x[k], host),
                           int(self.client_ranks[k]))
            for k in range(self.fcfg.num_clients)}

    # ------------------------------------------------------------- async/buff
    def _get_client_update_step(self):
        self._place_mesh_state()
        if self._client_update_step is None:
            fc = self.fcfg
            step = make_client_update_step(
                self.mcfg, self.ocfg, lora_scale=self.lora_scale,
                r_g=self.lcfg.rank, edit=fc.edit, aggregator=fc.aggregator,
                hetlora_prune_gamma=fc.hetlora_prune_gamma,
                mesh=self.client_mesh, n_sample=self._n_sample,
                faults=self.fault_schedule is not None)
            # donate the stacked adapters + ranks (scattered in-place);
            # global/prev_global stay live for later in-flight cohorts
            self._client_update_step = jax.jit(step, donate_argnums=(1, 4))
        return self._client_update_step

    def _get_merge_step(self):
        if self._merge_step is None:
            fc = self.fcfg
            step = make_buffer_merge_step(
                aggregator=fc.aggregator,
                staleness_decay=fc.staleness_decay,
                hetlora_beta=fc.hetlora_beta, lora_scale=self.lora_scale,
                guard=self.fault_schedule is not None)
            self._merge_step = jax.jit(step)
        return self._merge_step

    def run_round_async(self) -> dict:
        """One spanned tick of the buffered asynchronous timeline (see
        :meth:`_run_round_async_impl` for the mechanics)."""
        with self.telemetry.span("async_tick", cat="fed",
                                 tick=self._async_tick):
            return self._run_round_async_impl()

    def _run_round_async_impl(self) -> dict:
        """One tick of the buffered asynchronous (FedBuff-style) timeline:

        1. dispatch a fresh cohort of ``n_sample`` idle clients against the
           CURRENT global (tagged with the server version it saw);
        2. retire in-flight cohorts whose simulated delay
           (``FederatedConfig.async_delays``) has elapsed into the delta
           buffer — per client, as device-resident rows of the cohort's
           stacked update (no host round-trip);
        3. whenever ≥ M (= ``buffer_size`` or ``n_sample``) deltas are
           buffered, merge the M oldest through the ``fedbuff`` registry
           entry with per-delta staleness = current version − dispatch
           version, bumping the server version.

        With all delays 0 and M = n_sample this reduces tick-for-tick to the
        synchronous ``fedilora`` round (tested)."""
        fc = self.fcfg
        if fc.aggregator not in ("fedbuff", "fedbuff_kernel"):
            raise ValueError(
                f"run_round_async needs aggregator 'fedbuff' or "
                f"'fedbuff_kernel', got {fc.aggregator!r} (synchronous "
                "strategies cannot weight stale deltas)")
        delays = fc.async_delays
        if not delays and fc.measure_delays:
            delays = self.derived_async_delays()   # EMA-measured step times
        delays = delays or (0,) * fc.num_clients
        if len(delays) != fc.num_clients:
            raise ValueError(
                f"async_delays has {len(delays)} entries for "
                f"{fc.num_clients} clients")
        # drain a pending pipelined round before donating its buffers into
        # the client-update dispatch (same guard as run_round)
        self.flush_rounds()
        tick = self._async_tick
        n_s = self._n_sample
        rec: dict = {"tick": tick, "sampled": [], "merges": 0,
                     "staleness": [], "version": self._global_version}

        # ---- 1. dispatch a new cohort of idle clients --------------------
        busy = {e["client"] for e in self._inflight}
        avail = [k for k in range(fc.num_clients) if k not in busy]
        if len(avail) >= n_s:
            sampled = self._sample_clients(pool=avail, round_idx=tick)
            batch_idx = np.stack([self._batch_indices(self.clients[k])
                                  for k in sampled])
            co = None
            fault_args: tuple = ()
            if self.fault_schedule is not None:
                # async fault draws key on the TICK (the dispatch moment)
                co = self._fault_cohort(tick, sampled)
                fault_args = ({k: jnp.asarray(co[k])
                               for k in ("keep", "weight", "scale", "nan")},)
            measure = fc.measure_delays and \
                not self._ema_seen[list(map(int, sampled))].all()
            step = self._get_client_update_step()   # places mesh state first
            if fc.paged:
                # the cohort stays PINNED until it retires — its bank rows
                # hold the post-update adapters the eviction write-back
                # would otherwise have to capture mid-flight
                slots = self.store.acquire_cohort(sampled)
                idx = jnp.asarray(slots, jnp.int32)
                lora_in, ranks_in, sizes_in, data_in = (
                    self.store.lora_bank, self.store.ranks_bank,
                    self.store.sizes_bank, self.store.data_bank)
            else:
                idx = jnp.asarray(sampled, jnp.int32)
                lora_in, ranks_in, sizes_in, data_in = (
                    self.stacked_lora, self._ranks_dev, self._sizes_dev,
                    self._stacked_data)
            t0 = time.perf_counter()
            out = self._dispatch(
                "client_update", step, self.base_params, lora_in, self.server.global_lora,
                self.server.prev_global, ranks_in, sizes_in, data_in, idx,
                jnp.asarray(batch_idx, jnp.int32), *fault_args)
            if measure:
                # the wall clock needs the cohort finished: one sync per
                # tick — paid only while some sampled client is unmeasured
                # (the cohort time seeds those; it carries no per-client
                # signal for clients the reference loop already measured)
                jax.block_until_ready(out["update"])
                self._record_step_time(sampled, time.perf_counter() - t0,
                                       path="client_update",
                                       only_unseen=True)
            dropped = ([] if co is None else
                       [k for i, k in enumerate(sampled)
                        if co["keep"][i] <= 0])
            if fc.paged:
                self.store.adopt(out["stacked_lora"], out["ranks"])
                # dropped clients never scattered (in-engine masked index):
                # their rows are clean and retire immediately — unpin now
                self.store.mark_trained(
                    [k for k in sampled if k not in dropped])
                if dropped:
                    self.store.release_cohort(dropped)
            else:
                self.stacked_lora = out["stacked_lora"]
                self._ranks_dev = out["ranks"]
            # the buffer holds (cohort, row) references — hold only the
            # update halves so superseded stacked_lora buffers can free
            cohort = {"update": out["update"], "ranks": out["update_ranks"],
                      "sizes": out["update_sizes"],
                      "loss": out["metrics"]["last_loss"]}
            for i, k in enumerate(sampled):
                if co is not None and co["keep"][i] <= 0:
                    continue           # mid-round dropout: delta never lands
                extra = 0 if co is None else int(co["extra_ticks"][i])
                self._inflight.append({
                    "client": int(k), "row": i, "cohort": cohort,
                    "version": self._global_version,
                    "finish": tick + int(delays[k]) + extra})
            rec["sampled"] = list(map(int, sampled))
            if co is not None:
                self.health["n_dropped"] += int(co["n_dropped"])
                # async stragglers are DEFERRED (arrive staler), not
                # forfeited — count them separately from the sync timeline
                self.health["n_deferred"] += int(co["n_forfeited"])
                rec["health"] = {"n_dropped": int(co["n_dropped"]),
                                 "n_deferred": int(co["n_forfeited"])}

        # ---- 2. retire finished deltas into the buffer (arrival order) ---
        done = [e for e in self._inflight if e["finish"] <= tick]
        self._inflight = [e for e in self._inflight if e["finish"] > tick]
        self._buffer.extend(done)
        if fc.paged and done:
            # retirement = write-back point: unpin so the rows become
            # evictable (the dirty flag makes eviction capture them)
            self.store.release_cohort([e["client"] for e in done])

        # ---- 3. merge M-delta batches through the fedbuff registry -------
        M = fc.buffer_size or n_s
        merged_losses = []
        merge_health = []            # per-merge n_nonfinite (guarded merges)
        while len(self._buffer) >= M:
            batch, self._buffer = self._buffer[:M], self._buffer[M:]
            c0 = batch[0]["cohort"]
            if (M == int(c0["ranks"].shape[0])
                    and all(b["cohort"] is c0 for b in batch)
                    and [b["row"] for b in batch] == list(range(M))):
                # common case (zero delays, M = cohort): the WHOLE cohort's
                # stacked update passes through unsliced
                stacked, ranks_b, sizes_b = (c0["update"], c0["ranks"],
                                             c0["sizes"])
            else:                           # mixed cohorts: gather rows
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *[jax.tree_util.tree_map(lambda x, i=b["row"]: x[i],
                                             b["cohort"]["update"])
                      for b in batch])
                ranks_b = jnp.stack([b["cohort"]["ranks"][b["row"]]
                                     for b in batch])
                sizes_b = jnp.stack([b["cohort"]["sizes"][b["row"]]
                                     for b in batch])
            stal = np.asarray([self._global_version - b["version"]
                               for b in batch], np.float32)
            mo = self._dispatch(
                "buffer_merge", self._get_merge_step(), stacked, ranks_b,
                sizes_b, jnp.asarray(stal), self.server.global_lora)
            self.server.prev_global = mo["prev_global"]
            self.server.global_lora = mo["global_lora"]
            if "health" in mo:
                merge_health.append(mo["health"]["n_nonfinite"])
            self._global_version += 1
            self.server.round += 1
            rec["merges"] += 1
            rec["staleness"].extend(float(s) for s in stal)
            merged_losses.extend(b["cohort"]["loss"][b["row"]]
                                 for b in batch)
        if merged_losses:
            fetch = {"losses": merged_losses}
            if merge_health:
                fetch["nonfinite"] = merge_health
            if fc.paged:
                # ranks cannot change under fedbuff (no self-pruning) and
                # the bank-shaped [S] ranks are not the [K] host mirror —
                # fetch only the losses
                fetched = jax.device_get(fetch)
            else:
                fetch["ranks"] = self._ranks_dev
                fetched = jax.device_get(fetch)
                self.client_ranks = np.asarray(fetched["ranks"])
            rec["train_loss"] = float(np.mean(fetched["losses"]))
            if merge_health:
                nnf = int(np.sum(fetched["nonfinite"]))
                self.health["n_nonfinite"] += nnf
                rec.setdefault("health", {})["n_nonfinite"] = nnf
        rec["buffer_fill"] = len(self._buffer)
        self._async_tick += 1
        self.history.append(rec)
        return rec

    def run_round_reference(self) -> dict:
        """Host-driven per-client loop (the pre-fusion engine): one jit
        dispatch and one blocking ``float()`` sync per client, eager editing
        and pruning.  Kept as the numerical reference for
        fused-vs-reference tests and as the sequential benchmark baseline."""
        fc = self.fcfg
        sampled = self._sample_clients()
        r_g = self.lcfg.rank
        if self._local_train is None:
            self._local_train = jax.jit(self._local_train_impl)

        edited_layers, losses = [], []
        client_lora: dict[int, Pytree] = {}
        for k in sampled:
            c = self.clients[k]
            rank_k = int(self.client_ranks[k])
            if fc.aggregator == "flora":
                # FLoRA: server folded delta into base; clients restart LoRA
                lora0 = init_lora_params(
                    jax.random.PRNGKey(1000 * self.server.round + k),
                    self.specs, self.lcfg, client_rank=rank_k)
            else:
                lora0 = truncate_redistribute(self.server.global_lora, rank_k, r_g)
            batches = self._prefetch(c)
            t0 = time.perf_counter()
            lora1, ls = self._local_train(self.base_params, lora0, rank_k, batches)
            losses.append(float(ls[-1]))       # blocks on this client's steps
            if fc.measure_delays:
                self._record_step_time(k, time.perf_counter() - t0,
                                       path="local_train")
            # HetLoRA rank self-pruning (Cho et al. 2024): clients shrink
            # their rank when trailing dims carry negligible mass
            if fc.aggregator == "hetlora" and fc.hetlora_prune_gamma > 0:
                pruned = rank_k
                for entry in lora1.values():
                    pr = AG.hetlora_self_prune(entry, rank_k, r_g,
                                               fc.hetlora_prune_gamma)
                    pruned = min(pruned, int(pr))
                if pruned < rank_k:
                    rank_k = max(pruned, 1)
                    self.client_ranks[k] = rank_k
                    lora1 = mask_lora_params(lora1, rank_k, r_g)
            # --- layer-wise editing (before aggregation, paper Fig. 3) ------
            if fc.edit.enabled and fc.aggregator != "flora":
                glob_prev = truncate_redistribute(self.server.prev_global,
                                                  rank_k, r_g)
                lora1, diag = edit_lora(lora1, glob_prev, fc.edit)
                lora1 = mask_lora_params(lora1, rank_k, r_g)
                edited_layers.append(int(jnp.argmax(diag["selected"])))
            client_lora[k] = lora1

        # ---- stack once: aggregation input + one batched scatter ---------
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[client_lora[k] for k in sampled])
        if fc.paged:
            for k in sampled:
                self.store.write_client(k, client_lora[k],
                                        rank=int(self.client_ranks[k]))
        else:
            ks = np.asarray(sampled)
            self.stacked_lora = jax.tree_util.tree_map(
                lambda s, u: s.at[ks].set(u), self.stacked_lora, stacked)
            self._ranks_dev = jnp.asarray(self.client_ranks)

        # ---- aggregate (through the shared registry) ---------------------
        ranks = jnp.asarray([int(self.client_ranks[k]) for k in sampled])
        sizes = np.asarray([self.clients[k].size for k in sampled], np.float32)
        p = jnp.asarray(sizes / sizes.sum())

        # explicit snapshot — assigning the live global here would alias the
        # buffers the fused path donates (use-after-donate)
        self.server.prev_global = jax.tree_util.tree_map(
            jnp.copy, self.server.global_lora)
        agg_kw = {}
        if fc.aggregator in ("fedilora_clip", "fedilora_clip_kernel"):
            # the fused round anchors clipped-away mass on its input global;
            # prev_global IS that snapshot here — same anchor, same result
            agg_kw["anchor"] = self.server.prev_global
        global_new, base_delta = AG.aggregate(
            fc.aggregator, stacked, ranks, p,
            hetlora_beta=fc.hetlora_beta, lora_scale=self.lora_scale,
            clip=fc.clip_norm or None, trim=fc.trim_frac, **agg_kw)
        if base_delta is not None:         # flora
            self.base_params = apply_weight_deltas(self.base_params, base_delta)
            global_new = init_lora_params(
                jax.random.PRNGKey(self.server.round + 77), self.specs, self.lcfg)
        self.server.global_lora = global_new
        self.server.round += 1
        rec = {"round": self.server.round, "sampled": list(map(int, sampled)),
               "train_loss": float(np.mean(losses)),
               "edited_layers": edited_layers}
        self.history.append(rec)
        return rec

    # ------------------------------------------------------------------ eval
    def _next_logits_impl(self, base_params, toks, lora, pos, image):
        logits, _ = T.forward(self.mcfg, base_params, toks, lora=lora,
                              lora_scale=self.lora_scale, vision=image)
        return jnp.take_along_axis(
            logits, pos[None, None, None].astype(jnp.int32), axis=1)[:, 0]

    def _eval_loss_impl(self, base_params, lora, batch):
        _, m = T.loss_fn(self.mcfg, base_params, lora, batch, self.lora_scale)
        return m

    def _eval_batch(self, data: dict, n: int = 64) -> dict:
        sl = {k: jnp.asarray(v[:n]) for k, v in data.items()
              if k in ("tokens", "labels", "loss_mask", "image", "audio")}
        return sl

    def evaluate_global(self, generate: bool = True, n: int = 32) -> dict:
        m = self._dispatch("eval_loss", self._eval_loss, self.base_params,
                           self.server.global_lora,
                           self._eval_batch(self.global_test))
        out = {"loss": float(m["loss"]), "acc": float(m["acc"])}
        if generate:
            out.update(self.generation_scores(self.server.global_lora,
                                              self.global_test, n))
        return out

    def evaluate_personalized(self, generate: bool = True, n: int = 16,
                              loss_n: int = 64, vmapped: bool = True) -> dict:
        """Size-weighted average of client-local performance (paper Sec. 2.2).

        ``vmapped=True`` (default): the whole K-client sweep — eval loss AND
        KV-cached greedy decode on every client's personalized adapter — is
        ONE jitted dispatch, vmapped over the persistent stacked ``[K, ...]``
        state.  ``vmapped=False`` keeps the per-client host loop (~2
        dispatches per client) as the numerical reference and benchmark
        baseline.  Per-client row counts match the loop exactly: client k
        contributes ``min(loss_n, |shard_k|)`` loss rows and
        ``min(n, |shard_k|)`` generation rows; shorter shards are
        zero-padded in the rectangular stack, which is exact because the
        loss/acc are loss_mask-normalised (padded rows carry zero mask) and
        padded generation rows are sliced off before scoring."""
        w = np.asarray([c.size for c in self.clients], np.float64)
        w = w / w.sum()

        if not vmapped:
            accs, losses, bleus, rsums = [], [], [], []
            for c in self.clients:
                lora_k = c.lora        # one gather from the stacked state
                m = self._dispatch("eval_loss", self._eval_loss,
                                   self.base_params, lora_k,
                                   self._eval_batch(c.eval_data, loss_n))
                losses.append(float(m["loss"]));  accs.append(float(m["acc"]))
                if generate:
                    g = self.generation_scores(lora_k, c.eval_data, n)
                    bleus.append(g["bleu"]);  rsums.append(g["rsum"])
            out = {"loss": float(np.dot(w, losses)),
                   "acc": float(np.dot(w, accs))}
            if generate:
                out["bleu"] = float(np.dot(w, bleus))
                out["rsum"] = float(np.dot(w, rsums))
            return out

        # ---- one-dispatch population sweep over the stacked client axis --
        shard_rows = [c.eval_data["tokens"].shape[0] for c in self.clients]
        rows = min(max(n, loss_n), max(shard_rows))
        keys = [k for k in _EVAL_KEYS
                if all(k in c.eval_data for c in self.clients)]
        partial = [k for k in _EVAL_KEYS
                   if k not in keys and any(k in c.eval_data
                                            for c in self.clients)]
        if partial:
            raise ValueError(
                f"eval batch keys {partial} present in only some client "
                "shards; the stacked population eval needs uniform keys — "
                "add the key to every client or use vmapped=False")

        def _pad(x):
            # zero rows past a short shard: zero loss_mask ⇒ no metric
            # weight; padded generation rows are sliced off when scoring
            x = np.asarray(x)[:rows]
            if x.shape[0] < rows:
                x = np.pad(x, [(0, rows - x.shape[0])]
                           + [(0, 0)] * (x.ndim - 1))
            return x

        gen_rows = [min(n, r) for r in shard_rows]
        cap_start = gen_len = None
        if generate:
            lm = np.concatenate(
                [np.asarray(c.eval_data["loss_mask"])[:gen_rows[k]]
                 for k, c in enumerate(self.clients)])
            # uniformity across ALL clients' real rows: one static window
            cap_start, gen_len = _mask_decode_bounds(lm)

        if self.fcfg.paged:
            # ---- tiled paged sweep: the device never sees more than one
            # bank-sized [T, ...] adapter stack + eval batch at a time (T =
            # store slots) — one population_eval dispatch per tile, padded
            # tiles repeat client 0 and their rows are discarded
            K = len(self.clients)
            T = min(K, self.store.slots)
            self.store.flush()           # host tier now holds every row
            ck = ("paged", T, rows, loss_n, n, cap_start, gen_len,
                  "image" in keys)
            fn = self._pop_eval_cache.get(ck)
            if fn is None:
                fn = jax.jit(make_population_eval(
                    self.mcfg, lora_scale=self.lora_scale,
                    cap_start=cap_start, gen_len=gen_len,
                    loss_rows=min(loss_n, rows), gen_rows=min(n, rows),
                    generate=generate, mesh=None))
                self._pop_eval_cache[ck] = fn
            loss_v = np.zeros(K)
            acc_v = np.zeros(K)
            gens: list = [None] * K
            for t0 in range(0, K, T):
                ids = list(range(t0, min(t0 + T, K)))
                pad_ids = ids + [ids[0]] * (T - len(ids))
                lora_t = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *[self.store.host_adapter(k) for k in pad_ids])
                batch_t = {kk: jnp.asarray(np.stack(
                    [_pad(self.clients[k].eval_data[kk]) for k in pad_ids]))
                    for kk in keys}
                fetched = jax.device_get(self._dispatch(
                    "population_eval", fn, self.base_params, lora_t,
                    batch_t))
                for i, k in enumerate(ids):
                    loss_v[k] = fetched["loss"][i]
                    acc_v[k] = fetched["acc"][i]
                    if generate:
                        gens[k] = fetched["gen"][i]
            out = {"loss": float(np.dot(w, loss_v)),
                   "acc": float(np.dot(w, acc_v))}
            if generate:
                bleus, rsums = [], []
                for k, c in enumerate(self.clients):
                    nk = gen_rows[k]       # drop padded generation rows
                    sc = _score_generated(
                        gens[k][:nk],
                        np.asarray(c.eval_data["labels"][:nk]),
                        np.asarray(c.eval_data["loss_mask"][:nk]))
                    bleus.append(sc["bleu"])
                    rsums.append(sc["rsum"])
                out["bleu"] = float(np.dot(w, bleus))
                out["rsum"] = float(np.dot(w, rsums))
            return out

        batch = {k: jnp.stack([jnp.asarray(_pad(c.eval_data[k]))
                               for c in self.clients]) for k in keys}
        # shard the client axis over the configured mesh — the K
        # personalized evals then run device-parallel inside the single
        # dispatch (the per-client loop has no analogue of this).  On a 2-D
        # (client, "model") mesh each client group's eval additionally runs
        # tensor-parallel: base params are placed by param_spec and the
        # vmapped decode caches by cache_spec (spmd_axis_name threads the
        # client axis through the vmap).
        stacked = self.stacked_lora
        mesh = self.client_mesh
        client_ax = None
        if mesh is not None:
            from repro.sharding import round_mesh_axes
            client_ax, _ = round_mesh_axes(mesh)
        sharded = (mesh is not None
                   and len(self.clients) % mesh.shape[client_ax] == 0)
        if mesh is not None and not sharded:
            warnings.warn(
                f"client mesh {mesh} unusable for the population eval (need "
                f"a client axis whose size divides K={len(self.clients)}); "
                "running unsharded", stacklevel=2)
        if sharded:
            from jax.sharding import NamedSharding, PartitionSpec
            self._place_mesh_state()           # base params → param_spec
            stacked = self.stacked_lora
            spec = NamedSharding(mesh, PartitionSpec(client_ax))
            batch = jax.device_put(batch, spec)
            stacked = jax.device_put(stacked, spec)
        key = (len(self.clients), rows, loss_n, n, cap_start, gen_len,
               "image" in keys, mesh if sharded else None)
        fn = self._pop_eval_cache.get(key)
        if fn is None:
            fn = jax.jit(make_population_eval(
                self.mcfg, lora_scale=self.lora_scale, cap_start=cap_start,
                gen_len=gen_len, loss_rows=min(loss_n, rows),
                gen_rows=min(n, rows), generate=generate,
                mesh=mesh if sharded else None))
            self._pop_eval_cache[key] = fn
        fetched = jax.device_get(self._dispatch(
            "population_eval", fn, self.base_params, stacked, batch))
        out = {"loss": float(np.dot(w, fetched["loss"])),
               "acc": float(np.dot(w, fetched["acc"]))}
        if generate:
            bleus, rsums = [], []
            for k, c in enumerate(self.clients):
                nk = gen_rows[k]           # drop padded generation rows
                sc = _score_generated(
                    fetched["gen"][k][:nk],
                    np.asarray(c.eval_data["labels"][:nk]),
                    np.asarray(c.eval_data["loss_mask"][:nk]))
                bleus.append(sc["bleu"]);  rsums.append(sc["rsum"])
            out["bleu"] = float(np.dot(w, bleus))
            out["rsum"] = float(np.dot(w, rsums))
        return out

    def _generate_cached(self, lora, tokens: np.ndarray, image,
                         cap_start: int, gen_len: int) -> np.ndarray:
        """KV-cached greedy decode — one jit dispatch per generation call
        (prompt prefill + all decode steps are scanned inside the program)."""
        key = (tokens.shape[0], cap_start, gen_len, image is not None)
        fn = self._gen_cache.get(key)
        if fn is None:
            fn = jax.jit(make_greedy_generate(
                self.mcfg, lora_scale=self.lora_scale,
                cap_start=cap_start, gen_len=gen_len))
            self._gen_cache[key] = fn
        toks = jnp.asarray(tokens[:, : cap_start + 1])
        return np.asarray(self._dispatch("generate", fn, self.base_params,
                                         lora, toks, image))

    def generation_scores(self, lora, data: dict, n: int = 32,
                          cached: bool = True) -> dict:
        """Greedy caption generation → Google-BLEU / ROUGE-LSum (paper
        metrics).  ``cached=True`` uses the O(T) KV-cached decode;
        ``cached=False`` keeps the O(T²) full-forward-per-token reference
        (token-for-token identical, tested)."""
        tokens = np.asarray(data["tokens"][:n])
        labels = np.asarray(data["labels"][:n])
        loss_mask = np.asarray(data["loss_mask"][:n])
        image = jnp.asarray(data["image"][:n]) if "image" in data else None
        # prompt = everything before the first supervised position; the
        # window must be shared by every row (asserted, decode is static)
        cap_start, gen_len = _mask_decode_bounds(loss_mask)

        if cached:
            gen = self._generate_cached(lora, tokens, image, cap_start, gen_len)
        else:
            toks = np.array(tokens, copy=True)
            toks[:, cap_start + 1:] = 0
            toks = jnp.asarray(toks)
            cols = []
            for t in range(gen_len):
                pos = jnp.asarray(cap_start + t)
                lg = self._dispatch("next_logits", self._next_logits,
                                    self.base_params, toks, lora, pos, image)
                nxt = jnp.argmax(lg, -1)
                cols.append(nxt)               # device array: fetch ONCE below
                # teacher-force the token back only while it has a slot —
                # a window ending at the sequence boundary generates its
                # final token PAST the buffer (nothing consumes it, but an
                # out-of-bounds .at[].set would silently drop it from the
                # harvested window, shortening the scored caption)
                if cap_start + 1 + t < toks.shape[1]:
                    toks = toks.at[:, cap_start + 1 + t].set(
                        nxt.astype(toks.dtype))
            gen = np.asarray(jnp.stack(cols, axis=1))

        return _score_generated(gen, labels, loss_mask)
