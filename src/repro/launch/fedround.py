"""Federated round as ONE pjit program — the paper's technique distributed
TPU-natively (DESIGN.md §3: "clients → mesh data axis").

A communication round is expressed as a single SPMD computation:

    round_step(base_params, stacked_lora[K,...], ranks[K], p[K],
               batches[K, steps, B, ...])
        → (global_lora, edited_client_lora[K,...])

* the client axis K shards over ``data`` — every sampled client's local
  LoRA fine-tuning (a scanned AdamW loop) runs in parallel, one client
  group per data slice, with NO cross-client communication during local
  steps (base weights are read-only and tensor-parallel over ``model``);
* layer-wise editing (paper Eqs. 6-8) runs vmapped per client against the
  previous global adapter;
* FediLoRA's dimension-wise aggregation (Eqs. 3-5) is then a *masked
  weighted reduction over the data axis* — the parameter-server "upload +
  average" of the paper becomes a reduce/all-reduce collective in the
  compiled HLO, which the dry-run records.

Fused round engine
------------------

:func:`make_round_engine` builds the production ``round_step`` that
``repro.federated.FederatedTrainer.run_round`` actually executes — no longer
just a dry-run lowering target.  Differences from the plain
:func:`make_fed_round_step` lowering demo:

* operates on the trainer's *persistent* stacked client state
  (``stacked_lora[K_all, ...]`` + ``ranks[K_all]``): the sampled subset is
  gathered on device by index, trained/edited/pruned vmapped over the client
  axis, and scattered back — no per-client pytree restacking on the host;
* server-side redistribution (``truncate_redistribute``, or FLoRA's fresh
  re-init from a per-(round, client) fold of the PRNG) happens inside the
  program, so a round is exactly one jit dispatch;
* HetLoRA rank self-pruning is vectorised (``jnp.minimum`` reductions over
  modules under ``vmap``) instead of a host ``int()`` round-trip per module
  per client;
* aggregation dispatches through :data:`repro.core.aggregation.AGGREGATORS`
  (fedavg / hetlora / fedilora / fedilora_kernel / flora — the kernel entry
  lowers to the Pallas ``dim_agg`` kernel on TPU);
* the caller is expected to donate the stacked state
  (``stacked_lora, global_lora, prev_global, ranks``; plus ``base_params``
  for FLoRA) so the update is in-place on device. The *input* global adapter
  is passed through as the new ``prev_global`` output — an explicit snapshot
  that makes donation safe (no use-after-donate aliasing).

The host-driven loop survives as
``FederatedTrainer.run_round_reference`` — the numerical reference and the
sequential baseline that ``benchmarks/bench_fedround.py`` measures against.

Async / buffered engines
------------------------

Two further step builders decompose the fused round for the buffered
asynchronous (FedBuff-style) timeline driven by
``FederatedTrainer.run_round_async``:

* :func:`make_client_update_step` — the client half of ``round_step``
  (redistribute → gather batches → train/prune/edit → scatter back), WITHOUT
  server aggregation; it returns the sampled cohort's stacked update so the
  server can buffer it.  Each dispatch snapshots the global it trained
  against via its ``round_idx``/version tag on the host.
* :func:`make_buffer_merge_step` — the server half: merge a device-resident
  buffer of exactly ``M`` client deltas (stacked ``[M, ...]`` with ranks,
  sizes and per-delta staleness) into the current global through the
  ``fedbuff`` registry entry; the input global passes through as the new
  ``prev_global`` snapshot, exactly like the fused round.

Both halves share :func:`_make_client_phases` with ``make_round_engine`` —
the vmapped train → prune → edit pipeline (and its optional ``shard_map``
client-axis parallelism) is built once and reused.

2-D (client × model) meshes
---------------------------

Every engine accepts either a 1-D client mesh (``shard_map`` over the
client axis, exactly as before) or a 2-D mesh whose axes are
``(client, "model")``: sampled clients split over the client axis (pinned
by ``with_sharding_constraint`` on every per-client operand/result) while
GSPMD partitions each client group's forward/backward from the operands'
shardings — placing the frozen base weights with ``sharding.param_spec``
(tensor-parallel over ``"model"``, no FSDP: there is no data axis to
gather over, and frozen weights would pay an all-gather per use) makes the
local matmuls lower to psum collectives over ``"model"`` with the base
weights never gathered (HLO-tested).  LoRA adapters, optimizer state and
metrics stay replicated within a client group — they are the aggregation
objects.  Cohorts that don't divide the client axis are padded with
zero-weight dummy clients rather than falling back to a single device.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import aggregation as AG
from repro.core.editing import EditConfig, edit_lora
from repro.core.lora import (LoRAConfig, init_lora_params, mask_lora_params,
                             truncate_redistribute)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import OptimizerConfig, make_optimizer


def _make_local_train(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                      lora_scale: float, r_g: int) -> Callable:
    """One client's local fine-tuning: a scanned AdamW loop with gradients
    and iterates projected onto the client's rank subspace."""
    opt_init, opt_update = make_optimizer(opt_cfg)

    def local_train(base_params, lora0, rank, batches):
        opt = opt_init(lora0)

        def loss_of(lo, mb):
            loss, _ = T.loss_fn(cfg, base_params, lo, mb, lora_scale)
            return loss

        def step(carry, mb):
            lo, op = carry
            loss, g = jax.value_and_grad(loss_of)(lo, mb)
            g = mask_lora_params(g, rank, r_g)
            lo, op = opt_update(lo, g, op)
            lo = mask_lora_params(lo, rank, r_g)
            return (lo, op), loss

        (lora1, _), losses = lax.scan(step, (lora0, opt), batches)
        return lora1, losses

    return local_train


def _vmapped_self_prune(lora, ranks, r_g: int, gamma: float):
    """HetLoRA rank self-pruning over the stacked client axis — pure lax
    (the reference loop's per-module host ``int()`` round-trips, vectorised)."""

    def _prune_one(lo, rank):
        pruned = rank
        for entry in lo.values():
            pruned = jnp.minimum(
                pruned, AG.hetlora_self_prune(entry, rank, r_g, gamma))
        pruned = jnp.maximum(pruned, 1)
        return mask_lora_params(lo, pruned, r_g), pruned

    return jax.vmap(_prune_one)(lora, ranks)


def _vmapped_edit(lora, ranks, prev_global, edit: EditConfig, r_g: int):
    """Layer-wise editing (paper Eqs. 6-8) vmapped over the client axis;
    returns (edited stacked lora, edited-module index per client)."""

    def _edit_one(lo, rank):
        glob_prev = truncate_redistribute(prev_global, rank, r_g)
        edited, diag = edit_lora(lo, glob_prev, edit)
        return (mask_lora_params(edited, rank, r_g),
                jnp.argmax(diag["selected"]).astype(jnp.int32))

    return jax.vmap(_edit_one)(lora, ranks)


def cohort_pad(n_sample: int, mesh) -> int:
    """Padded cohort size: the next multiple of the mesh's client-axis size.

    When ``n_sample`` doesn't divide over the client axis the engines pad
    the sampled-client axis with zero-weight dummy clients (``p = 0``,
    masked metrics, dropped scatters) instead of silently falling back to
    single-device execution — see :func:`make_round_engine`."""
    if mesh is None:
        return n_sample
    from repro.sharding import round_mesh_axes
    client_ax, _ = round_mesh_axes(mesh)
    n_client = mesh.shape[client_ax]
    return -(-n_sample // n_client) * n_client


def _pad_cohort(idx, batch_idx, n_pad: int, n_total: int):
    """Pad ``(idx[n_s], batch_idx[n_s, ...])`` to ``n_pad`` rows with dummy
    clients.  Dummies carry the out-of-range index ``n_total`` — gathers go
    through a clipped copy (they read the last real client's data, wasted
    but harmless compute) while scatters use the raw index with
    ``mode="drop"`` so dummies never write back.  Returns
    ``(idx, clipped_idx, batch_idx, valid[n_pad])``."""
    n_s = idx.shape[0]
    if n_pad > n_s:
        idx = jnp.concatenate(
            [idx, jnp.full((n_pad - n_s,), n_total, idx.dtype)])
        batch_idx = jnp.concatenate(
            [batch_idx,
             jnp.zeros((n_pad - n_s,) + batch_idx.shape[1:], batch_idx.dtype)])
    valid = jnp.arange(n_pad) < n_s
    return idx, jnp.clip(idx, 0, n_total - 1), batch_idx, valid


def _make_client_phases(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                        lora_scale: float, r_g: int, edit: EditConfig,
                        edit_active: bool, prune_active: bool,
                        hetlora_prune_gamma: float,
                        mesh=None, n_sample: int | None = None) -> Callable:
    """Build the per-client half shared by the fused round and the async
    client-update step: ``(base_params, prev_global, lora0, ranks_s,
    batches) -> (lora1, ranks_s, metrics)``, vmapped over the client axis.

    ``mesh`` (optional, 1-D or 2-D — see ``sharding.round_mesh_axes``):

    * 1-D: the phases wrap in ``shard_map`` with the sampled-client axis
      split over the mesh (callers pad the cohort to a multiple of its
      size via :func:`cohort_pad`) — unchanged from the original
      client-parallel round, bit-identical;
    * 2-D ``(client, "model")``: GSPMD partitioning with the client axis
      pinned by ``with_sharding_constraint`` on every per-client operand
      and result, while inside each client group the local AdamW
      forward/backward is partitioned over ``"model"`` by propagation from
      the operands' shardings (``sharding.param_spec`` places the frozen
      base weights tensor-parallel over ``"model"``) — the TP matmuls
      lower to psum collectives and the base weights are never gathered,
      while LoRA adapters/optimizer state stay replicated per group (they
      are the aggregation objects).  A partial-manual ``shard_map``
      (client manual, model auto) would express the same program, but
      ``lax.scan`` inside a manual-subgroup region trips XLA's partitioner
      (``IsManualSubgroup`` check), so the 2-D path is constraint-driven
      GSPMD end to end."""
    local_train = _make_local_train(cfg, opt_cfg, lora_scale=lora_scale,
                                    r_g=r_g)

    def _client_phases(base_params, prev_global, lora0, ranks_s, batches):
        """train → prune → edit, vmapped over the (local) client axis.
        Each phase runs under a ``jax.named_scope`` — pure metadata for
        profiler/HLO readability (op names gain the phase prefix), zero
        effect on lowering or numerics."""
        with jax.named_scope("fedround.local_train"):
            lora1, losses = jax.vmap(
                lambda lo, r, b: local_train(base_params, lo, r, b)
            )(lora0, ranks_s, batches)
            metrics = {"last_loss": losses[:, -1]}
        if prune_active:
            with jax.named_scope("fedround.prune"):
                lora1, ranks_s = _vmapped_self_prune(lora1, ranks_s, r_g,
                                                     hetlora_prune_gamma)
        if edit_active:
            with jax.named_scope("fedround.edit"):
                lora1, edited = _vmapped_edit(lora1, ranks_s, prev_global,
                                              edit, r_g)
                metrics["edited"] = edited
        return lora1, ranks_s, metrics

    if mesh is not None and n_sample is None:
        raise ValueError(
            "a round mesh needs n_sample (the static sampled-cohort size) "
            "to shard the client axis — pass n_sample=... or drop mesh= "
            "(silently running single-device on a configured mesh would "
            "be an expensive no-op)")
    if mesh is None:
        return _client_phases

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import round_mesh_axes
    ax, model_ax = round_mesh_axes(mesh)        # raises on malformed meshes
    if model_ax is None:
        return jax.shard_map(
            _client_phases, mesh=mesh,
            in_specs=(P(), P(), P(ax), P(ax), P(ax)),
            out_specs=(P(ax), P(ax), P(ax)), check_vma=False)

    row = NamedSharding(mesh, P(ax))

    def sharded_phases(base_params, prev_global, lora0, ranks_s, batches):
        con = lambda t: jax.lax.with_sharding_constraint(t, row)
        lora1, ranks_out, metrics = _client_phases(
            base_params, prev_global, con(lora0), con(ranks_s), con(batches))
        return con(lora1), con(ranks_out), con(metrics)

    return sharded_phases


def make_fed_round_step(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                        lora_scale: float, r_g: int,
                        edit: EditConfig | None = None,
                        aggregator: str = "fedilora",
                        hetlora_beta: float = 1.0) -> Callable:
    """The single-SPMD round used by the ``--fedround`` dry-run: already
    gathered/sampled inputs, LoRA-space aggregators only (FLoRA folds dense
    deltas into the base weights — use :func:`make_round_engine`)."""
    edit = edit or EditConfig()
    local_train = _make_local_train(cfg, opt_cfg, lora_scale=lora_scale, r_g=r_g)
    if aggregator == "flora":
        raise ValueError("flora updates base weights; use make_round_engine")

    def round_step(base_params, stacked_lora, prev_global, ranks, p, batches):
        # --- parallel local fine-tuning: client axis on "data" -------------
        lora1, losses = jax.vmap(
            lambda lo, r, b: local_train(base_params, lo, r, b)
        )(stacked_lora, ranks, batches)

        # --- layer-wise editing vs previous global (per client) ------------
        if edit.enabled:
            lora1, _ = _vmapped_edit(lora1, ranks, prev_global, edit, r_g)

        # --- aggregation = reduction over the data (client) axis -----------
        global_new, _ = AG.aggregate(aggregator, lora1, ranks, p,
                                     hetlora_beta=hetlora_beta,
                                     lora_scale=lora_scale)
        return global_new, lora1, jnp.mean(losses[:, -1])

    return round_step


def _broadcast_rows(v, x):
    """Broadcast a per-client vector [K] against a stacked leaf [K, ...]."""
    return v.reshape((-1,) + (1,) * (x.ndim - 1))


def _rows_finite(tree):
    """Per-client all-leaves-finite reduction over a stacked pytree → bool
    [K].  One corrupted (NaN/Inf) element anywhere in a client's update
    marks the whole client."""
    fins = [jnp.all(jnp.isfinite(x), axis=tuple(range(1, x.ndim)))
            for x in jax.tree_util.tree_leaves(tree)]
    out = fins[0]
    for f in fins[1:]:
        out = jnp.logical_and(out, f)
    return out


def _sanitize_rows(tree, finite):
    """Zero whole client rows that carry non-finite values.  A ``where``,
    not a multiply: ``0 * NaN`` is NaN, so zeroing the aggregation weight
    alone would still poison every weighted reduction."""
    return jax.tree_util.tree_map(
        lambda x: jnp.where(_broadcast_rows(finite, x), x,
                            jnp.zeros_like(x)), tree)


def _pad_fault(fault, n_pad: int):
    """Pad the per-cohort fault operand vectors with neutral entries so
    dummy (cohort-padding) rows read as healthy non-participants."""
    n = fault["keep"].shape[0]
    if n >= n_pad:
        return fault
    ext = lambda v, fill: jnp.concatenate(
        [v, jnp.full((n_pad - n,), fill, v.dtype)])
    return {"keep": ext(fault["keep"], 1.0),
            "weight": ext(fault["weight"], 1.0),
            "scale": ext(fault["scale"], 1.0),
            "nan": ext(fault["nan"], 0.0)}


def make_round_engine(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                      specs, lora_scale: float, r_g: int,
                      edit: EditConfig | None = None,
                      aggregator: str = "fedilora",
                      hetlora_beta: float = 1.0,
                      hetlora_prune_gamma: float = 0.0,
                      mesh=None, n_sample: int | None = None,
                      clip: float | None = None, trim: float = 0.0,
                      faults: bool = False) -> Callable:
    """Build the production fused round over the trainer's persistent
    stacked state.  Returned signature::

        round_step(base_params, stacked_lora[K,...], global_lora,
                   prev_global, ranks[K] i32, sizes[K] f32,
                   data {key: [K, N, ...]}, idx[n_s] i32, cids[n_s] i32,
                   batch_idx[n_s, steps, B] i32, round_idx i32) -> dict

    ``idx`` indexes rows of the stacked state — GLOBAL client ids for the
    resident ``[K, ...]`` trainer, bank SLOTS for the paged
    ``ClientStateStore`` trainer (the math is row-local either way, so the
    two are bit-identical).  ``cids`` always carries the global client ids
    of the cohort: FLoRA's fresh per-(round, client) re-init folds the
    client IDENTITY into its PRNG, which must not change when rows move
    between bank slots (resident callers pass ``cids == idx``).

    ``data`` is the device-resident training corpus stacked over ALL
    clients (shards zero-padded to the longest); the round's minibatches
    are gathered *inside* the program from ``(idx, batch_idx)``, so batch
    tensors never transit the host.  Output keys: ``stacked_lora``
    (scattered update), ``global_lora``, ``prev_global`` (the *input*
    global, snapshotted for next round's editing), ``ranks``
    (post-pruning), ``metrics`` (``last_loss[n_s]``, optional
    ``edited[n_s]``) and — for FLoRA only — ``base_params`` with the dense
    deltas folded in.  All phases run in one jit program; ``aggregator``
    selects the compiled variant statically.

    ``mesh``: optional device mesh, 1-D (pure client parallelism) or 2-D
    ``(client, "model")`` (client groups × tensor-parallel local training —
    see :func:`_make_client_phases`).  When the client-axis size doesn't
    divide ``n_sample`` the sampled-client axis is padded inside the
    program with zero-weight dummy clients (``p = 0`` so every aggregator
    ignores them, metrics sliced back to ``n_sample``, scatters dropped)
    instead of falling back to single-device execution.

    ``clip``/``trim`` parameterise the robust registry entries
    (``fedilora_clip`` / ``fedilora_trimmed``); the previous global anchors
    the clipped-away mass.  ``faults=True`` appends one trailing operand —
    ``fault = {keep, weight, scale, nan}``, four f32[n_s] vectors from
    ``federated.faults.FaultSchedule.cohort`` — and the round absorbs every
    injected fault *in-program*, still one jit dispatch:

    * ``keep == 0`` (mid-round dropout): the client's trained update is
      neither aggregated nor scattered back — its persistent row keeps the
      pre-round state, exactly like the zero-weight dummy-client pattern;
    * ``weight == 0`` with ``keep == 1`` (straggler forfeited by the round
      deadline): the update IS scattered back (the client finished, too
      late to merge) but carries zero aggregation weight;
    * ``scale``/``nan`` corrupt the *wire copy* entering aggregation
      (``u·scale + nan`` — sign flips, scaled outliers, NaN/Inf poison)
      while the client's stored adapter stays clean;
    * a per-client non-finite reduction zeroes poisoned rows (data AND
      weight) before aggregation, the surviving weights renormalise, and a
      fully-dead cohort falls back to the previous global;
    * ``out["health"]`` carries ``n_dropped / n_forfeited / n_nonfinite /
      clip_rate`` back through the round's existing single metrics fetch.

    With ``faults=False`` (the default) the engine signature and program
    are exactly the pre-fault ones — the zero-fault timeline is trivially
    bit-identical.

    The round's phases run under named scopes (HLO metadata only):
    ``fedround.gather`` (batch gather and redistribution), the client
    phases' ``fedround.local_train`` / ``prune`` / ``edit``,
    ``fedround.aggregate`` (fault absorption and the registry's
    aggregation) and ``fedround.scatter`` (into the persistent stack).
    """
    edit = edit or EditConfig()
    lcfg = LoRAConfig(rank=r_g)
    edit_active = edit.enabled and aggregator != "flora"
    prune_active = aggregator == "hetlora" and hetlora_prune_gamma > 0
    n_pad = cohort_pad(n_sample, mesh) if (mesh is not None
                                           and n_sample is not None) else None
    client_phases = _make_client_phases(
        cfg, opt_cfg, lora_scale=lora_scale, r_g=r_g, edit=edit,
        edit_active=edit_active, prune_active=prune_active,
        hetlora_prune_gamma=hetlora_prune_gamma, mesh=mesh,
        n_sample=n_pad)

    def aggregate(agg_lora, ranks_s, p, agg_kw):
        return AG.aggregate(aggregator, agg_lora, ranks_s, p,
                            hetlora_beta=hetlora_beta, lora_scale=lora_scale,
                            clip=clip, trim=trim, **agg_kw)

    if mesh is not None and aggregator.endswith("_kernel"):
        # the *_kernel registry entries reduce in a Mosaic kernel, which
        # GSPMD cannot partition: gather the cohort's updates and run the
        # whole (tiny) aggregation on every device
        from jax.sharding import PartitionSpec as P
        aggregate = jax.shard_map(aggregate, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False)

    def round_step(base_params, stacked_lora, global_lora, prev_global,
                   ranks, sizes, data, idx, cids, batch_idx, round_idx,
                   fault=None):
        n_s = idx.shape[0]
        idx, gidx, batch_idx, valid = _pad_cohort(
            idx, batch_idx, n_pad or n_s, ranks.shape[0])
        if cids.shape[0] < idx.shape[0]:   # dummy ids match the dummy idx
            cids = jnp.concatenate(
                [cids, jnp.full((idx.shape[0] - cids.shape[0],),
                                ranks.shape[0], cids.dtype)])
        ranks_s = ranks[gidx]
        # dummy rows carry zero weight: every registry strategy multiplies
        # by p, so padded clients cannot perturb the aggregate
        sizes_s = jnp.where(valid, sizes[gidx], 0.0)
        if not faults:
            p = sizes_s / jnp.maximum(jnp.sum(sizes_s), 1e-12)

        with jax.named_scope("fedround.gather"):
            # --- device-side batch gather: [n_s, steps, B, ...] ------------
            batches = {k: v[gidx[:, None, None], batch_idx]
                       for k, v in data.items()}

            # --- server → client redistribution (on device) ----------------
            if aggregator == "flora":
                # FLoRA: server folded last round's delta into base; clients
                # restart from a fresh per-(round, client) init (Wang et al.)
                def _init(k):
                    return init_lora_params(
                        jax.random.PRNGKey(1000 * round_idx + k), specs, lcfg)

                lora0 = jax.vmap(
                    lambda k, r: mask_lora_params(_init(k), r, r_g))(
                        cids, ranks_s)
            else:
                lora0 = jax.vmap(lambda r: truncate_redistribute(
                    global_lora, r, r_g))(ranks_s)

        # --- per-client phases, parallel over the client axis --------------
        lora1, ranks_s, metrics = client_phases(
            base_params, prev_global, lora0, ranks_s, batches)

        with jax.named_scope("fedround.aggregate"):
            # --- fault absorption (wire corruption + health guards) ---------
            agg_lora = lora1
            scatter_idx = idx
            health = None
            agg_kw = {}
            if aggregator in ("fedilora_clip", "fedilora_clip_kernel"):
                agg_kw["anchor"] = global_lora   # clipped-away mass stays here
            if faults:
                f = _pad_fault(fault, idx.shape[0])
                # corruption hits the wire copy only — the client's stored
                # adapter (scattered below) stays clean
                agg_lora = jax.tree_util.tree_map(
                    lambda x: (x * _broadcast_rows(f["scale"], x).astype(x.dtype)
                               + _broadcast_rows(f["nan"], x).astype(x.dtype)),
                    lora1)
                finite = _rows_finite(agg_lora)
                agg_lora = _sanitize_rows(agg_lora, finite)
                sizes_agg = (sizes_s * f["weight"]
                             * finite.astype(sizes_s.dtype))
                p = sizes_agg / jnp.maximum(jnp.sum(sizes_agg), 1e-12)
                # dropped clients never write back: their scatter index goes
                # out of range, mode="drop" discards it (the dummy-client
                # idiom)
                scatter_idx = jnp.where(f["keep"] > 0, idx, ranks.shape[0])
                agg_kw["fallback"] = global_lora
                vf = valid.astype(jnp.float32)
                alive = vf * (f["keep"] > 0) * (f["weight"] > 0)
                if AG._clip_active(clip):
                    norms = AG.client_update_norms(agg_lora)
                    part = alive * finite.astype(jnp.float32)
                    clip_rate = (jnp.sum(part * (norms > clip))
                                 / jnp.maximum(jnp.sum(part), 1.0))
                else:
                    clip_rate = jnp.float32(0.0)
                health = {
                    "n_dropped": jnp.sum(vf * (f["keep"] <= 0)),
                    "n_forfeited": jnp.sum(vf * (f["keep"] > 0)
                                           * (f["weight"] <= 0)),
                    "n_nonfinite": jnp.sum(alive * (1.0 - finite.astype(
                        jnp.float32))),
                    "clip_rate": clip_rate,
                }

            # --- aggregation through the shared registry -------------------
            global_new, base_delta = aggregate(agg_lora, ranks_s, p, agg_kw)

        with jax.named_scope("fedround.scatter"):
            # scatter the sampled clients back into the persistent stack
            # (mode="drop" — the jax default — discards dummy rows, whose
            # index is out of bounds by construction)
            stacked_new = jax.tree_util.tree_map(
                lambda s, u: s.at[scatter_idx].set(u, mode="drop"),
                stacked_lora, lora1)
            ranks_new = ranks.at[scatter_idx].set(ranks_s, mode="drop")
        out = {
            "stacked_lora": stacked_new,
            "ranks": ranks_new,
            # the input global becomes prev_global: an explicit pass-through
            # output, so donation of the input buffer stays safe
            "prev_global": global_lora,
            "metrics": jax.tree_util.tree_map(lambda m: m[:n_s], metrics),
        }
        if health is not None:
            out["health"] = health
        if base_delta is not None:  # flora
            out["base_params"] = apply_weight_deltas(base_params, base_delta)
            global_new = init_lora_params(
                jax.random.PRNGKey(round_idx + 77), specs, lcfg)
        out["global_lora"] = global_new
        return out

    return round_step


def make_client_update_step(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                            lora_scale: float, r_g: int,
                            edit: EditConfig | None = None,
                            aggregator: str = "fedbuff",
                            hetlora_prune_gamma: float = 0.0,
                            mesh=None, n_sample: int | None = None,
                            faults: bool = False) -> Callable:
    """Client half of the fused round for the buffered-async timeline::

        client_update_step(base_params, stacked_lora[K,...], global_lora,
                           prev_global, ranks[K], sizes[K],
                           data {key: [K, N, ...]}, idx[n_s],
                           batch_idx[n_s, steps, B]) -> dict

    Redistributes the (possibly stale) global to the sampled cohort, gathers
    minibatches in-program, runs the shared train → prune → edit pipeline and
    scatters the personalized adapters back — but performs NO aggregation:
    the cohort's stacked ``update`` (plus ``update_ranks``/``update_sizes``)
    is returned for the server to buffer, and the merge happens later in
    :func:`make_buffer_merge_step` once ``M`` deltas have accumulated.
    FLoRA's fresh re-init is deliberately unsupported here (it rewrites base
    weights synchronously, which has no buffered-async analogue).  Pruning
    and editing are gated exactly like :func:`make_round_engine` so the
    zero-staleness timeline stays equivalent to the synchronous round.

    ``faults=True`` appends a trailing ``fault = {keep, weight, scale, nan}``
    operand: dropped clients (``keep == 0``) don't scatter their trained
    state back, and corruption hits the buffered ``update`` rows (the wire)
    while the scattered local state stays clean.  Poisoned rows are caught
    later by the merge guard (:func:`make_buffer_merge_step`), mirroring a
    real deployment where the server validates at merge time.
    """
    edit = edit or EditConfig()
    if aggregator == "flora":
        raise ValueError("flora updates base weights; it has no "
                         "buffered-async client half")
    n_pad = cohort_pad(n_sample, mesh) if (mesh is not None
                                           and n_sample is not None) else None
    client_phases = _make_client_phases(
        cfg, opt_cfg, lora_scale=lora_scale, r_g=r_g, edit=edit,
        edit_active=edit.enabled,
        prune_active=aggregator == "hetlora" and hetlora_prune_gamma > 0,
        hetlora_prune_gamma=hetlora_prune_gamma, mesh=mesh,
        n_sample=n_pad)

    def client_update_step(base_params, stacked_lora, global_lora,
                           prev_global, ranks, sizes, data, idx, batch_idx,
                           fault=None):
        n_s = idx.shape[0]
        idx, gidx, batch_idx, _ = _pad_cohort(
            idx, batch_idx, n_pad or n_s, ranks.shape[0])
        ranks_s = ranks[gidx]
        sizes_s = sizes[gidx]
        batches = {k: v[gidx[:, None, None], batch_idx]
                   for k, v in data.items()}
        lora0 = jax.vmap(
            lambda r: truncate_redistribute(global_lora, r, r_g))(ranks_s)
        lora1, ranks_s, metrics = client_phases(
            base_params, prev_global, lora0, ranks_s, batches)
        update = jax.tree_util.tree_map(lambda x: x[:n_s], lora1)
        scatter_idx = idx
        if faults:
            f = _pad_fault(fault, idx.shape[0])
            # wire-level corruption of the buffered rows; the scattered
            # local state stays clean (the merge guard catches the poison)
            update = jax.tree_util.tree_map(
                lambda x: x * _broadcast_rows(f["scale"][:n_s], x).astype(
                    x.dtype)
                + _broadcast_rows(f["nan"][:n_s], x).astype(x.dtype), update)
            scatter_idx = jnp.where(f["keep"] > 0, idx, ranks.shape[0])
        # dummy rows (padded cohorts) are sliced off everything the server
        # buffers and dropped from the scatters
        return {
            "stacked_lora": jax.tree_util.tree_map(
                lambda s, u: s.at[scatter_idx].set(u, mode="drop"),
                stacked_lora, lora1),
            "ranks": ranks.at[scatter_idx].set(ranks_s, mode="drop"),
            "update": update,                 # [n_s, ...] delta to buffer
            "update_ranks": ranks_s[:n_s],
            "update_sizes": sizes_s[:n_s],
            "metrics": jax.tree_util.tree_map(lambda m: m[:n_s], metrics),
        }

    return client_update_step


def make_buffer_merge_step(*, aggregator: str = "fedbuff",
                           staleness_decay: float = 0.5,
                           hetlora_beta: float = 1.0,
                           lora_scale: float = 1.0,
                           guard: bool = False) -> Callable:
    """Server half of the buffered-async round::

        merge_step(buffer_lora[M,...], buf_ranks[M], buf_sizes[M],
                   buf_staleness[M] f32, global_lora) -> dict

    Merges exactly ``M`` buffered client deltas into the current global
    through the :data:`repro.core.aggregation.AGGREGATORS` registry
    (``fedbuff`` / ``fedbuff_kernel`` consume the per-delta staleness and
    anchor on the current global; synchronous entries ignore them).  The
    input global passes through as the new ``prev_global`` snapshot —
    donation-safe exactly like ``round_step``.  ``M`` is static (jit once
    per buffer size).

    ``guard=True`` (fault-injected trainers) validates the buffer at merge
    time: rows with any non-finite element are zeroed (data and weight),
    the surviving weights renormalise, a fully-poisoned buffer falls back
    to the previous global, and ``out["health"]["n_nonfinite"]`` reports
    the count through the merge's metrics fetch.
    """
    if aggregator == "flora":
        raise ValueError("flora has no buffered-async merge (dense base "
                         "deltas cannot be staleness-discounted in LoRA space)")

    def merge_step(buffer_lora, buf_ranks, buf_sizes, buf_staleness,
                   global_lora):
        agg_kw = {}
        health = None
        if guard:
            finite = _rows_finite(buffer_lora)
            buffer_lora = _sanitize_rows(buffer_lora, finite)
            buf_sizes = buf_sizes * finite.astype(buf_sizes.dtype)
            agg_kw["fallback"] = global_lora
            health = {"n_nonfinite": jnp.sum(1.0 - finite.astype(
                jnp.float32))}
        p = buf_sizes / jnp.maximum(jnp.sum(buf_sizes), 1e-12)
        global_new, _ = AG.aggregate(
            aggregator, buffer_lora, buf_ranks, p,
            hetlora_beta=hetlora_beta, lora_scale=lora_scale,
            staleness=buf_staleness, anchor=global_lora,
            staleness_decay=staleness_decay)
        out = {"global_lora": global_new, "prev_global": global_lora}
        if health is not None:
            out["health"] = health
        return out

    return merge_step


def apply_weight_deltas(params, deltas: dict):
    """Fold FLoRA dense deltas {spec_name: [L, out, in]} into base weights."""
    params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    for name, delta in deltas.items():
        upd = jnp.swapaxes(delta, -1, -2)  # [L, in, out]
        if name.startswith("enc."):
            node = params["encoder"]["blocks"]["s0"]
            path = name.split(".")[1:]
        else:
            sub, rest = name.split(".", 1)
            node = params["blocks"][sub]
            path = rest.split(".")
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = node[path[-1]] + upd.astype(node[path[-1]].dtype)
    return params
