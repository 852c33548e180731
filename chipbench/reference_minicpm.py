"""Plain reference of MiniCPM's µP decoder, in float32.

Written from the published description alone (arXiv:2404.06395 §4 and the
model's ``modeling_minicpm.py``) and importing nothing of the program: the
pre-norm decoder of ``reference.py`` (RMSNorm, rotary attention, SwiGLU,
tied unembedding, LoRA on the query and value projections) with MiniCPM's
three µP scalings:

* the token embedding × ``scale_emb`` (12);
* each attention and MLP branch × ``scale_depth / √layers`` (1.4/√40)
  before its residual add;
* the final-normed hidden state ÷ (``d / dim_model_base``) (2304/256 = 9)
  before the tied unembedding.

No attention bias.  The arithmetic (``reference.Prec``: float32 at
``highest``, or the fp8 control) and the building blocks are
``reference.py``'s, reused unchanged, as are its editing, aggregation,
rank masks and protocol order; the local training and the rounds are
``reference.py``'s with this model's loss.  Weights stay in the dtype they
were made in and are upcast one layer at a time.  Each client of a cohort
trains whole on one chip; the cohort's clients are spread over the chips
given (``fed_rounds``' ``devices``), one program on each, with nothing
exchanged between them.  ``dm`` is ``model.dims(config)`` with the config's
``scale_emb``, ``scale_depth`` and ``dim_model_base`` added.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from chipbench.reference import (Prec, _proj, aggregate, edit,
                                 protocol_batches, rank_mask, rms_norm, rope)


def _layer(P, dm, scale, x, lw, lo):
    f32 = lambda t: t.astype(jnp.float32)
    B, S, _ = x.shape
    h, kv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    branch = dm["scale_depth"] / math.sqrt(dm["layers"])
    a = lw["attn"]
    hn = rms_norm(x, f32(lw["ln1"]), dm["eps"])
    q = _proj(P, hn, f32(a["wq"]), lo.get("wq"), scale)
    k = _proj(P, hn, f32(a["wk"]), None, scale)
    v = _proj(P, hn, f32(a["wv"]), lo.get("wv"), scale)
    pos = jnp.arange(S)
    q = rope(q.reshape(B, S, h, hd), pos, dm["theta"])
    k = rope(k.reshape(B, S, kv, hd), pos, dm["theta"])
    v = v.reshape(B, S, kv, hd)
    rep = h // kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = P.mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = P.mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + branch * P.mm("bsi,io->bso", o.reshape(B, S, h * hd),
                          f32(a["wo"]))
    h2 = rms_norm(x, f32(lw["ln2"]), dm["eps"])
    f = lw["ffn"]
    g = P.mm("bsi,io->bso", h2, f32(f["w1"]))
    u = P.mm("bsi,io->bso", h2, f32(f["w3"]))
    return x + branch * P.mm("bsi,io->bso", jax.nn.silu(g) * u, f32(f["w2"]))


def hidden(P, dm, base, lora, tokens, scale):
    """Final-normed hidden states [B, S, d], before the logit scaling;
    ``lora`` as in ``reference.hidden``."""
    x = base["embed"][tokens].astype(jnp.float32) * dm["scale_emb"]
    lo = {name.split(".")[-1]: ab for name, ab in lora.items()}
    layer = jax.checkpoint(lambda x, xs: (_layer(P, dm, scale, x, *xs), None))
    x, _ = lax.scan(layer, x, (base["blocks"]["s0"], lo))
    return rms_norm(x, base["final_ln"].astype(jnp.float32), dm["eps"])


def logits(P, dm, base, lora, tokens, scale):
    h = hidden(P, dm, base, lora, tokens, scale)
    return P.mm("bsd,vd->bsv", h / (dm["d"] / dm["dim_model_base"]),
                base["embed"])


def loss(P, dm, base, lora, batch, scale, half: bool = False):
    """Masked next-token cross-entropy; ``half`` plants the fault of
    ``reference.loss``."""
    toks, labels, mask = batch["tokens"], batch["labels"], batch["loss_mask"]
    if half:
        if toks.shape[0] > 1:
            n = toks.shape[0] // 2
            toks, labels, mask = toks[:n], labels[:n], mask[:n]
        else:
            seen = jnp.cumsum(mask, axis=1)
            mask = mask * (seen <= jnp.sum(mask, axis=1, keepdims=True) / 2)
    lp = jax.nn.log_softmax(logits(P, dm, base, lora, toks, scale), -1)
    ll = jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------- federated

def local_train(dm, hp, prec: str = "f32", half: bool = False):
    """(base, lora0, rank, batches[steps, ...]) -> (lora, last loss): local
    AdamW as in ``reference.make_local_train``."""
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

    return run


def make_cohort_train(dm, hp, mesh: Mesh, prec: str = "f32",
                      half: bool = False):
    """jit: (base, lora0s [n, ...], ranks [n], batches [n, steps, ...]) ->
    (loras [n, ...], last losses [n]), the n clients split over the mesh's
    one axis and trained one after another on each chip."""
    run = local_train(dm, hp, prec, half)
    ax = mesh.axis_names[0]

    def block(base, lora0, ranks, batches):
        return lax.map(lambda xs: run(base, *xs), (lora0, ranks, batches))

    spec = PartitionSpec(ax)
    return jax.jit(jax.shard_map(
        block, mesh=mesh, in_specs=(PartitionSpec(), spec, spec, spec),
        out_specs=(spec, spec), check_vma=False))


def fed_rounds(dm, hp, base, shards, g0, seed, rounds, prec="f32",
               half=False, devices=None):
    """``reference.fed_rounds`` for this model: the same protocol and the
    same returns.  Each round's cohort trains in one call spread over
    ``devices`` (default: the first device); the largest count that
    divides the cohort is used."""
    devices = list(devices or jax.devices()[:1])
    n = hp["n_sample"]
    mesh = Mesh(np.asarray(devices[:math.gcd(n, len(devices))]), ("c",))
    rows = NamedSharding(mesh, PartitionSpec("c"))
    base = jax.device_put(base, NamedSharding(mesh, PartitionSpec()))
    train = make_cohort_train(dm, hp, mesh, prec, half)
    sizes = [s["tokens"].shape[0] for s in shards]
    plan = protocol_batches(seed, sizes, rounds, n, hp["batch"], hp["steps"])
    g, prev = g0, g0
    losses, cohorts, first_clients, globals_, edited = [], [], {}, [], []
    for r, (cohort, idx) in enumerate(plan):
        ranks = [hp["ranks"][k] for k in cohort]
        start = jax.tree_util.tree_map(
            lambda *x: np.stack(x),
            *[jax.device_get(rank_mask(g, rk, hp["r_g"])) for rk in ranks])
        batches = {key: np.stack([shards[k][key][idx[k]] for k in cohort])
                   for key in shards[0]}
        out, vals = train(base, *jax.device_put(
            (start, np.asarray(ranks, np.int32), batches), rows))
        out, vals = jax.device_get((out, vals))
        loras, sel = [], []
        for i, k in enumerate(cohort):
            lo = jax.tree_util.tree_map(lambda x: jnp.asarray(x[i]), out)
            if hp["edit"]:
                lo, s = edit(lo, rank_mask(prev, ranks[i], hp["r_g"]))
                lo = rank_mask(lo, ranks[i], hp["r_g"])
                sel.append(s)
            loras.append(lo)
            if r == 0:
                first_clients[k] = jax.device_get(lo)
        new = aggregate(loras, ranks, [sizes[k] for k in cohort], hp["r_g"])
        prev, g = g, new
        losses.append(float(np.mean(vals)))
        cohorts.append(cohort)
        edited.append(sel)
        globals_.append(jax.device_get(g))
    return {"loss": losses, "cohorts": cohorts, "clients": first_clients,
            "globals": globals_, "edited": edited}
