"""jit-able step functions: train_step (LoRA fine-tuning with microbatched
gradient accumulation + remat), prefill_step, serve_step (one-token decode).

These are the lowering targets of the multi-pod dry-run and the bodies of the
federated round: in FediLoRA only the LoRA adapters train — base weights are
frozen inputs, so there is no base-gradient reduce-scatter and the optimizer
state is adapter-sized.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import OptimizerConfig, make_optimizer


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                    lora_scale: float, num_microbatches: int = 1,
                    remat: bool = True, act_spec=None, moe_spec=None) -> Callable:
    """(params, lora, opt_state, batch) -> (lora', opt_state', metrics).

    ``act_spec``: optional sequence-parallel residual-stream PartitionSpec
    (hillclimb lever, see EXPERIMENTS.md §Perf)."""
    _, update_fn = make_optimizer(opt_cfg)

    def loss_of(lora, params, mb):
        return T.loss_fn(cfg, params, lora, mb, lora_scale, remat=remat,
                         act_spec=act_spec, moe_spec=moe_spec)

    def train_step(params, lora, opt_state, batch):
        if num_microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(
                lora, params, batch)
        else:
            def split(x):
                return x.reshape((num_microbatches, x.shape[0] // num_microbatches)
                                 + x.shape[1:])

            mb_batch = jax.tree_util.tree_map(split, batch)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_of, has_aux=True)(lora, params, mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), m

            zeros = jax.tree_util.tree_map(jnp.zeros_like, lora)
            (g_sum, loss_sum), ms = lax.scan(acc, (zeros, jnp.zeros((), jnp.float32)),
                                             mb_batch)
            grads = jax.tree_util.tree_map(lambda g: g / num_microbatches, g_sum)
            loss = loss_sum / num_microbatches
            metrics = jax.tree_util.tree_map(lambda x: jnp.mean(x, 0), ms)
        lora_new, opt_new = update_fn(lora, grads, opt_state)
        metrics = dict(metrics)
        metrics["total_loss"] = loss
        return lora_new, opt_new, metrics

    return train_step


def make_eval_step(cfg: ModelConfig, *, lora_scale: float) -> Callable:
    def eval_step(params, lora, batch):
        _, metrics = T.loss_fn(cfg, params, lora, batch, lora_scale)
        return metrics

    return eval_step


def make_prefill_step(cfg: ModelConfig, *, lora_scale: float) -> Callable:
    """(params, lora, batch) -> last-position logits [B, V] (f32).
    The unembed runs on the final position only (no [B,S,V] materialisation)."""

    def prefill_step(params, lora, batch):
        logits, _ = T.forward(cfg, params, batch["tokens"], lora=lora,
                              lora_scale=lora_scale, vision=batch.get("image"),
                              audio=batch.get("audio"), last_only=True)
        return logits[:, 0].astype(jnp.float32)

    return prefill_step


def make_serve_step(cfg: ModelConfig, *, lora_scale: float,
                    moe_spec=None, seq_axis=None) -> Callable:
    """(params, lora, cache, tokens, pos) -> (logits [B,V], cache').

    ``embeds`` (optional [B,1,d]) replaces the token embedding for the step —
    the cached-prefill path streams vision-prefix vectors through it."""

    def serve_step(params, lora, cache, tokens, pos, embeds=None):
        return T.decode_step(cfg, params, cache, tokens, pos, lora=lora,
                             lora_scale=lora_scale, moe_spec=moe_spec,
                             seq_axis=seq_axis, embeds=embeds)

    return serve_step


def _bank_for_scan(adapters, layout: str):
    """Normalise an adapter bank to scan-major [L, G, ...] leaves (the block
    scan strips L exactly like the single-adapter tree; enc.* entries don't
    serve).  ``layout="scan"`` means the caller already holds that shape
    (e.g. ``AdapterStore.scan_stack``, transposed once per page-in) —
    transposing slot-major [G, L, ...] here instead would materialise a
    whole-bank copy inside EVERY jitted dispatch."""
    if layout == "scan":
        return adapters
    return {k: jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1), v)
            for k, v in adapters.items() if k.startswith("s")}


def make_multi_adapter_serve_step(cfg: ModelConfig, *, lora_scale: float,
                                  lora_backend: str = "gather",
                                  bank_layout: str = "slot") -> Callable:
    """One-token decode where EVERY BATCH ROW uses its own LoRA adapter:

        ``(params, adapters[G,...], adapter_idx[B], cache, embeds[B,d],
           pos[B]) -> (logits [B, V], cache')``

    ``adapters`` is a stacked bank (leaves ``[G, ...]``, e.g. an
    AdapterStore's device stack); row ``b`` applies adapter
    ``adapter_idx[b]`` — the BGMV formulation of multi-tenant LoRA serving.
    ``pos`` is per-row (a continuous-batching engine's slots sit at
    different sequence positions); the whole batch runs through ONE
    ``T.decode_chunk`` call with per-row positions — no per-row vmap, and
    no per-row copy of the full adapter tree.

    ``lora_backend``:

    * ``"gather"`` — each LoRA site gathers only its tiny per-row (A, B)
      pair and contracts row-wise (jnp; XLA fuses the gather);
    * ``"grouped"`` — the Pallas BGMV kernel
      (``kernels/lora_gather_matmul.py``): the per-row index is a
      scalar-prefetch operand steering the A/B BlockSpec DMA, so the
      gather happens in the memory system (interpret mode on the CPU
      backend only).

    Both are mathematically identical to running each row through
    ``make_serve_step`` with its own adapter (tested).  ``bank_layout``:
    ``"slot"`` = leaves [G, L, ...] (an AdapterStore's mutation-side stack,
    transposed in-program), ``"scan"`` = already scan-major [L, G, ...]
    (``AdapterStore.scan_stack`` — the hot-path layout)."""
    kernel = {"gather": False, "grouped": True}[lora_backend]

    def multi_serve_step(params, adapters, adapter_idx, cache, embeds, pos):
        bank = _bank_for_scan(adapters, bank_layout)
        return T.decode_chunk(cfg, params, cache, embeds[:, None, :], pos,
                              adapters=bank, adapter_idx=adapter_idx,
                              lora_scale=lora_scale, lora_kernel=kernel)

    return multi_serve_step


def make_chunked_prefill_step(cfg: ModelConfig, *, lora_scale: float,
                              chunk: int, n_prefix: int = 0,
                              lora_backend: str = "gather",
                              bank_layout: str = "slot",
                              flash: bool | None = None) -> Callable:
    """Chunked multi-token prefill over a ServingEngine's slot state:

        ``(params, adapters[G,...], state, cache) -> (state', cache')``

    ONE dispatch pushes up to ``chunk`` teacher-forced positions of every
    prefill-phase slot (``pos < plen - 1``) through the decode-cache write
    path: a ``[B, chunk, d]`` embedding block (per-slot mux of
    vision-prefix vectors and prompt tokens) runs through ``T.decode_chunk``
    at per-slot ragged offsets, intra-chunk causal attention reuses
    ``multihead_attention``'s chunked online-softmax path (``flash``: None
    = auto by size, True = force, False = naive), ragged tails are masked
    (their cache rows stay untouched), and NO logits are computed — prefill
    positions' logits are discarded anyway, so the unembed matmul is
    skipped entirely.  A P-position prompt therefore fills its slot's cache
    rows in ⌈P/chunk⌉ dispatches instead of P serial serve_steps (P =
    n_prefix + prompt_len − 1; the last teacher-forced position belongs to
    the first decode step, which emits the first token).

    ``state`` is the engine's slot-state dict (ptoks/vis/aidx/pos/plen/
    tlen); slots already past prefill (or free) advance by zero positions
    and keep their cache rows bit-identical."""
    kernel = {"gather": False, "grouped": True}[lora_backend]

    def prefill_step(params, adapters, state, cache):
        pos, plen, tlen = state["pos"], state["plen"], state["tlen"]
        B = pos.shape[0]
        offs = pos[:, None] + jnp.arange(chunk)                  # [B, C]
        valid = (offs < (plen - 1)[:, None]) & (tlen > 0)[:, None]
        Sp = state["ptoks"].shape[1]
        tok_pos = jnp.clip(offs - n_prefix, 0, Sp - 1)
        toks = jnp.take_along_axis(state["ptoks"], tok_pos, axis=1)
        embeds = T.embed_tokens(cfg, params, toks)              # [B, C, d]
        if n_prefix:
            rows = jnp.arange(B)[:, None]
            pre = state["vis"][rows, jnp.clip(offs, 0, n_prefix - 1)]
            embeds = jnp.where((offs < n_prefix)[..., None],
                               pre.astype(embeds.dtype), embeds)
        bank = _bank_for_scan(adapters, bank_layout)
        _, cache = T.decode_chunk(cfg, params, cache, embeds, pos,
                                  adapters=bank, adapter_idx=state["aidx"],
                                  lora_scale=lora_scale, valid=valid,
                                  lora_kernel=kernel, logits=False,
                                  chunked=flash)
        return dict(state, pos=pos + valid.sum(1).astype(pos.dtype)), cache

    return prefill_step


def make_greedy_generate(cfg: ModelConfig, *, lora_scale: float,
                         cap_start: int, gen_len: int,
                         cache_sharding: Callable | None = None) -> Callable:
    """KV-cached greedy caption generation:
    ``(params, lora, tokens[B,S], vision?) -> gen[B, gen_len]``.

    Evaluation decode used to re-run a full O(S²) forward per generated
    token; this builds the O(T) path instead: the prompt (vision prefix +
    text up to ``cap_start``) is streamed through ``serve_step`` once to fill
    the cache (a ``lax.scan``, so the whole generation is ONE dispatch when
    jitted), then ``gen_len`` cached single-token decode steps run greedily.
    Token-for-token identical to the uncached argmax loop (tested).

    ``cap_start``/``gen_len`` are static — jit once per evaluation shape.
    ``cache_sharding``: optional cache-tree → cache-tree placement hook
    (e.g. a ``with_sharding_constraint`` built from ``sharding.cache_spec``)
    applied to the freshly initialised decode cache — the population sweep
    uses it to pin per-client caches onto a 2-D mesh.
    """
    serve_step = make_serve_step(cfg, lora_scale=lora_scale)

    def generate(params, lora, tokens, vision=None):
        B = tokens.shape[0]
        xs = T.embed_tokens(cfg, params, tokens[:, : cap_start + 1])  # [B, P, d]
        n_prefix = 0
        if vision is not None and cfg.family == "vlm" and cfg.vision_mode == "prefix":
            pre = vision.astype(xs.dtype) @ params["vision_proj"]
            xs = jnp.concatenate([pre, xs], axis=1)
            n_prefix = pre.shape[1]
        cache = T.init_cache(
            cfg, params, B, n_prefix + cap_start + 1 + gen_len,
            vision=vision if cfg.vision_mode == "cross" else None)
        if cache_sharding is not None:
            cache = cache_sharding(cache)

        def prefill(carry, inp):
            x_t, t = inp
            logits, carry = serve_step(params, lora, carry, None, t,
                                       embeds=x_t[:, None, :])
            return carry, logits

        cache, logits = lax.scan(
            prefill, cache,
            (jnp.swapaxes(xs, 0, 1), jnp.arange(xs.shape[1])))
        tok0 = jnp.argmax(logits[-1], -1).astype(jnp.int32)

        def step(carry, t):
            tok, c = carry
            lg, c = serve_step(params, lora, c, tok, n_prefix + cap_start + t)
            nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            return (nxt, c), nxt

        (_, _), rest = lax.scan(step, (tok0, cache),
                                jnp.arange(1, gen_len))     # [gen_len-1, B]
        return jnp.concatenate([tok0[None], rest], axis=0).swapaxes(0, 1)

    return generate


def _population_mesh_tools(mesh):
    """(client_axis, cache-placement hook) for a population sweep mesh.

    The hook constrains a per-client decode cache with ``sharding.
    cache_spec`` (feature dims over ``"model"`` where divisible; batch/seq
    rules degrade on axes the mesh doesn't carry); the client axis itself
    is threaded through the vmap via ``spmd_axis_name`` so the stacked
    ``[K, ...]`` caches land split over the client axis with their inner
    dims placed by the spec."""
    if mesh is None:
        return None, None
    from repro.sharding import round_mesh_axes, tree_cache_shardings
    client_ax, _ = round_mesh_axes(mesh)

    def cache_sharding(cache):
        return jax.lax.with_sharding_constraint(
            cache, tree_cache_shardings(cache, mesh))

    return client_ax, cache_sharding


def make_population_generate(cfg: ModelConfig, *, lora_scale: float,
                             cap_start: int, gen_len: int,
                             mesh=None) -> Callable:
    """KV-cached greedy decode vmapped over a stacked client axis:
    ``(params, stacked_lora[K,...], tokens[K,B,S], vision[K,B,...]?) ->
    gen[K, B, gen_len]``.

    The personalized evaluation sweep used to walk all K clients with one
    generate dispatch each; this collapses the population into ONE jitted
    dispatch over the trainer's persistent stacked ``[K, ...]`` adapter
    state (base params broadcast, per-client KV caches batched by vmap).
    Token-for-token identical to the per-client loop (tested).

    ``mesh``: optional 1-D / 2-D ``(client, "model")`` mesh — the vmapped
    population axis shards over the client axis (``spmd_axis_name``) and
    the per-client decode caches are placed by ``sharding.cache_spec``."""
    client_ax, cache_sharding = _population_mesh_tools(mesh)
    gen = make_greedy_generate(cfg, lora_scale=lora_scale,
                               cap_start=cap_start, gen_len=gen_len,
                               cache_sharding=cache_sharding)

    def population_generate(params, stacked_lora, tokens, vision=None):
        vm = lambda f: jax.vmap(f, spmd_axis_name=client_ax)
        if vision is None:
            return vm(lambda lo, t: gen(params, lo, t))(stacked_lora, tokens)
        return vm(lambda lo, t, v: gen(params, lo, t, v)
                  )(stacked_lora, tokens, vision)

    return population_generate


def make_population_eval(cfg: ModelConfig, *, lora_scale: float,
                         cap_start: int | None = None,
                         gen_len: int | None = None,
                         loss_rows: int | None = None,
                         gen_rows: int | None = None,
                         generate: bool = True, mesh=None) -> Callable:
    """The full personalized evaluation sweep as ONE program:
    ``(params, stacked_lora[K,...], batch {key: [K, rows, ...]}) ->
    {"loss"[K], "acc"[K], "gen"[K, gen_rows, gen_len]?}``.

    Eval loss (over the first ``loss_rows`` rows) and the KV-cached greedy
    decode (first ``gen_rows`` rows) are vmapped together over the client
    axis, so evaluating all K personalized adapters is a single jit call
    instead of ~2K.  ``generate=False`` drops the decode half.  ``mesh``:
    optional population mesh — client axis through ``spmd_axis_name``,
    decode caches placed by ``sharding.cache_spec`` (see
    :func:`make_population_generate`)."""
    client_ax, cache_sharding = _population_mesh_tools(mesh)
    gen_fn = None
    if generate:
        gen_fn = make_greedy_generate(cfg, lora_scale=lora_scale,
                                      cap_start=cap_start, gen_len=gen_len,
                                      cache_sharding=cache_sharding)

    def population_eval(params, stacked_lora, batch):
        def one_client(lora, b):
            lb = b if loss_rows is None else \
                {k: v[:loss_rows] for k, v in b.items()}
            _, m = T.loss_fn(cfg, params, lora, lb, lora_scale)
            out = {"loss": m["loss"], "acc": m["acc"]}
            if gen_fn is not None:
                toks = b["tokens"] if gen_rows is None else \
                    b["tokens"][:gen_rows]
                vis = b.get("image")
                if vis is not None and gen_rows is not None:
                    vis = vis[:gen_rows]
                out["gen"] = gen_fn(params, lora, toks, vis)
            return out

        return jax.vmap(one_client, spmd_axis_name=client_ax)(
            stacked_lora, batch)

    return population_eval
