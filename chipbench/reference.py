"""Plain reference of what the timed paths compute, in float32.

Written from the published descriptions alone and importing nothing of the
program: a pre-norm decoder (RMSNorm, rotary attention with grouped KV
heads and optional q/k/v bias, SwiGLU MLP, tied unembedding) with LoRA on
the query and value projections (``y = x W + s (x Aᵀ) Bᵀ``); the
FediLoRA round (arXiv:2509.06984): redistribution truncated to each
client's rank, local AdamW with the gradient projected onto the rank and
clipped to global norm 1, layer-wise editing of the least similar A
module (Eqs. 6-8), and dimension-wise aggregation (Eqs. 3-5).

Every matrix product goes through ``Prec.mm``: float32 at ``highest``
(the TPU otherwise takes a single bfloat16 pass), or, for the control, fp8
(e4m3) operands with a per-tensor scale and float32 accumulation, forward
and backward — the next precision below the configuration's bfloat16.  Weights stay in the
dtype they were made in and are upcast one layer at a time inside the
layer scan, so the reference fits next to nothing else on the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(x):
    """Round to e4m3 with a per-tensor scale (back in float32)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HI)


def _mm8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return jnp.einsum(spec, qa, qb, precision=HI), (qa, qb)


def _mm8_bwd(spec, res, g):
    """The backward products in fp8 too: the cotangent gets its own scale
    (an unscaled cast would flush it to zero)."""
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(spec, a, b, precision=HI), *res)
    return vjp(_fp8(g))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


class Prec:
    """Arithmetic of the reference (``"f32"``) or of its control
    (``"fp8"``)."""

    def __init__(self, name: str = "f32"):
        if name not in ("f32", "fp8"):
            raise ValueError(f"precision {name!r}")
        self.name = name

    def mm(self, spec: str, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.name == "fp8":
            return _mm8(spec, a, b)
        return jnp.einsum(spec, a, b, precision=HI)


# ------------------------------------------------------------------ model

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: [B, S, H, D]; rotates the two halves of D by position."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(P, x, w, ab, scale):
    y = P.mm("bsi,io->bso", x, w)
    if ab is not None:
        xa = P.mm("bsi,ri->bsr", x, ab["A"])
        y = y + scale * P.mm("bsr,or->bso", xa, ab["B"])
    return y


def _layer(P, dm, scale, x, lw, lo):
    f32 = lambda t: t.astype(jnp.float32)
    B, S, _ = x.shape
    h, kv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    a = lw["attn"]
    hn = rms_norm(x, f32(lw["ln1"]), dm["eps"])
    q = _proj(P, hn, f32(a["wq"]), lo.get("wq"), scale)
    k = _proj(P, hn, f32(a["wk"]), None, scale)
    v = _proj(P, hn, f32(a["wv"]), lo.get("wv"), scale)
    if dm["bias"]:
        q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
    pos = jnp.arange(S)
    q = rope(q.reshape(B, S, h, hd), pos, dm["theta"])
    k = rope(k.reshape(B, S, kv, hd), pos, dm["theta"])
    v = v.reshape(B, S, kv, hd)
    rep = h // kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = P.mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = P.mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + P.mm("bsi,io->bso", o.reshape(B, S, h * hd), f32(a["wo"]))
    h2 = rms_norm(x, f32(lw["ln2"]), dm["eps"])
    f = lw["ffn"]
    g = P.mm("bsi,io->bso", h2, f32(f["w1"]))
    u = P.mm("bsi,io->bso", h2, f32(f["w3"]))
    return x + P.mm("bsi,io->bso", jax.nn.silu(g) * u, f32(f["w2"]))


def hidden(P, dm, base, lora, tokens, scale):
    """Final-normed hidden states [B, S, d]; ``lora`` maps site names
    (``s0.attn.wq``/``s0.attn.wv``) to stacked {"A": [L, r, in],
    "B": [L, out, r]}."""
    x = base["embed"][tokens].astype(jnp.float32)
    blocks = base["blocks"]["s0"]
    lo = {name.split(".")[-1]: ab for name, ab in lora.items()}
    layer = jax.checkpoint(lambda x, xs: (_layer(P, dm, scale, x, *xs), None))
    x, _ = lax.scan(layer, x, (blocks, lo))
    return rms_norm(x, base["final_ln"].astype(jnp.float32), dm["eps"])


def logits(P, dm, base, lora, tokens, scale):
    return P.mm("bsd,vd->bsv", hidden(P, dm, base, lora, tokens, scale),
                base["embed"])


def loss(P, dm, base, lora, batch, scale, half: bool = False):
    """Masked next-token cross-entropy.  ``half``: the fault of a step that
    leaves out half of its batch and takes the mean over the rest (half of
    the rows, or, for a batch of one row, half of its loss positions)."""
    toks, labels, mask = batch["tokens"], batch["labels"], batch["loss_mask"]
    if half:
        if toks.shape[0] > 1:
            n = toks.shape[0] // 2
            toks, labels, mask = toks[:n], labels[:n], mask[:n]
        else:
            seen = jnp.cumsum(mask, axis=1)
            mask = mask * (seen <= jnp.sum(mask, axis=1, keepdims=True) / 2)
    lg = logits(P, dm, base, lora, toks, scale)
    lp = jax.nn.log_softmax(lg, -1)
    ll = jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------- federated

def rank_mask(tree, rank, r_g):
    m = (jnp.arange(r_g) < rank).astype(jnp.float32)
    return {n: {"A": e["A"] * m[None, :, None], "B": e["B"] * m[None, None, :]}
            for n, e in tree.items()}


def make_local_train(dm, hp, prec: str = "f32", half: bool = False):
    """jit: (base, lora0, rank, batches[steps, ...]) -> (lora, last loss)."""
    P = Prec(prec)
    r_g, lr, scale = hp["r_g"], hp["lr"], hp["scale"]
    b1, b2, eps = 0.9, 0.999, 1e-8

    def run(base, lora0, rank, batches):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, lora0)

        def step(carry, xs):
            lo, m, v = carry
            t, mb = xs
            val, g = jax.value_and_grad(
                lambda l: loss(P, dm, base, l, mb, scale, half))(lo)
            g = rank_mask(g, rank, r_g)
            gn = jnp.sqrt(sum(jnp.sum(x * x)
                              for x in jax.tree_util.tree_leaves(g)))
            c = jnp.where(gn > 1.0, 1.0 / jnp.maximum(gn, 1e-12), 1.0)
            g = jax.tree_util.tree_map(lambda x: x * c, g)
            m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b,
                                       v, g)
            tf = t.astype(jnp.float32)
            lo = jax.tree_util.tree_map(
                lambda p, a, b: p - lr * (a / (1 - b1 ** tf))
                / (jnp.sqrt(b / (1 - b2 ** tf)) + eps), lo, m, v)
            return (rank_mask(lo, rank, r_g), m, v), val

        steps = jax.tree_util.tree_leaves(batches)[0].shape[0]
        (lo, _, _), vals = lax.scan(step, (lora0, zeros, zeros),
                                    (jnp.arange(1, steps + 1), batches))
        return lo, vals[-1]

    return jax.jit(run)


def edit(local, prev_trunc):
    """Blend the least similar A module (over sites in sorted order ×
    layers) toward the previous global by its own cosine similarity.
    Returns the edited adapter and the module's index."""
    names = sorted(local)
    sims = []
    for n in names:
        a, g = local[n]["A"], prev_trunc[n]["A"]
        dot = jnp.sum(a * g, axis=(1, 2))
        nn = jnp.sqrt(jnp.sum(a * a, (1, 2))) * jnp.sqrt(jnp.sum(g * g, (1, 2)))
        sims.append(dot / jnp.maximum(nn, 1e-12))
    sims = jnp.concatenate(sims)
    sel = int(jnp.argmin(sims))
    gamma = sims[sel]
    out = {n: dict(e) for n, e in local.items()}
    off = 0
    for n in names:
        L = local[n]["A"].shape[0]
        if off <= sel < off + L:
            l = sel - off
            a = local[n]["A"]
            out[n]["A"] = a.at[l].set(gamma * a[l]
                                      + (1 - gamma) * prev_trunc[n]["A"][l])
        off += L
    return out, sel


def aggregate(loras, ranks, sizes, r_g):
    """Dimension-wise weighted mean: rank dimension d of the global takes
    the clients whose rank covers d, their data shares renormalised."""
    p = np.asarray(sizes, np.float64) / np.sum(sizes)
    cover = (np.arange(r_g)[None, :] < np.asarray(ranks)[:, None]) * p[:, None]
    w = jnp.asarray(cover / np.maximum(cover.sum(0, keepdims=True), 1e-12),
                    jnp.float32)
    out = {}
    for n in loras[0]:
        A = jnp.stack([lo[n]["A"] for lo in loras])
        B = jnp.stack([lo[n]["B"] for lo in loras])
        out[n] = {"A": jnp.einsum("kd,kldn->ldn", w, A, precision=HI),
                  "B": jnp.einsum("kd,klmd->lmd", w, B, precision=HI)}
    return out


def protocol_batches(seed: int, sizes, rounds: int, n_sample: int,
                     batch: int, steps: int):
    """The protocol's cohort and batch order: cohort ``sorted(choice(K, n,
    replace=False))`` from a generator seeded with the run's seed; each
    client draws its minibatches as consecutive slices of fresh
    permutations of its shard from a generator seeded ``seed + 7k + 1``."""
    K = len(sizes)
    server = np.random.default_rng(seed)
    client = [np.random.default_rng(seed + 7 * k + 1) for k in range(K)]
    out = []
    for _ in range(rounds):
        cohort = sorted(int(k) for k in server.choice(K, n_sample,
                                                       replace=False))
        idx = {}
        for k in cohort:
            rows = []
            while len(rows) < steps:
                perm = client[k].permutation(sizes[k])
                for i in range(0, sizes[k] - batch + 1, batch):
                    rows.append(perm[i:i + batch])
                    if len(rows) == steps:
                        break
            idx[k] = np.stack(rows)
        out.append((cohort, idx))
    return out


def fed_rounds(dm, hp, base, shards, g0, seed, rounds, prec="f32",
               half=False):
    """``rounds`` FediLoRA rounds from the global ``g0``.  Returns the
    per-round mean last-step loss, the cohort of each round, the
    post-edit adapter of each round-1 client, the global after each
    round, and the module each client edited."""
    train = make_local_train(dm, hp, prec, half)
    sizes = [s["tokens"].shape[0] for s in shards]
    plan = protocol_batches(seed, sizes, rounds, hp["n_sample"],
                            hp["batch"], hp["steps"])
    g, prev = g0, g0
    losses, cohorts, first_clients, globals_, edited = [], [], {}, [], []
    for r, (cohort, idx) in enumerate(plan):
        loras, last, sel = [], [], []
        for k in cohort:
            rk = hp["ranks"][k]
            batches = {key: jnp.asarray(v[idx[k]])
                       for key, v in shards[k].items()}
            lo, val = train(base, rank_mask(g, rk, hp["r_g"]), rk, batches)
            if hp["edit"]:
                lo, s = edit(lo, rank_mask(prev, rk, hp["r_g"]))
                lo = rank_mask(lo, rk, hp["r_g"])
                sel.append(s)
            loras.append(lo)
            last.append(float(val))
            if r == 0:
                first_clients[k] = jax.device_get(lo)
        new = aggregate(loras, [hp["ranks"][k] for k in cohort],
                        [sizes[k] for k in cohort], hp["r_g"])
        prev, g = g, new
        losses.append(float(np.mean(last)))
        cohorts.append(cohort)
        edited.append(sel)
        globals_.append(jax.device_get(g))
    return {"loss": losses, "cohorts": cohorts, "clients": first_clients,
            "globals": globals_, "edited": edited}


# ------------------------------------------------------------- serving

def make_served_gap(dm, scale, prec: str = "f32"):
    """jit: (base, adapter, tokens [1, S], at [G], served [G], valid [G])
    -> the widest gap, over the valid positions ``at``, between the best
    float32 logit and that of the served token (``prec="f32"``) or of the
    token the reference in ``prec`` ranks first (the control)."""
    P, low = Prec("f32"), Prec(prec)

    def gap(base, lora, toks, at, served, valid):
        def at_logits(p):
            h = hidden(p, dm, base, lora, toks, scale)[0, at]
            return p.mm("gd,vd->gv", h, base["embed"])
        lg = at_logits(P)
        pick = served if prec == "f32" else jnp.argmax(at_logits(low), -1)
        g = jnp.max(lg, -1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return jnp.max(jnp.where(valid, g, 0.0))

    return jax.jit(gap)
