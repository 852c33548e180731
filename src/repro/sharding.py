"""Parameter / activation partition rules for the production mesh.

Mesh axes: ``("data", "model")`` single-pod 16×16, ``("pod", "data", "model")``
multi-pod 2×16×16.  Strategy (DESIGN.md §5):

* 2D-sharded weights: tensor-parallel over ``model`` on the "parallel" matmul
  dim, FSDP over ``data`` on the other large dim (base weights are frozen in
  federated LoRA fine-tuning — FSDP costs one all-gather per layer and no
  grad reduce-scatter);
* LoRA adapters, norms, biases, small tables: replicated (they are the
  federated aggregation objects and <2% of bytes);
* batch sharded over ``("pod", "data")`` when divisible; for batch=1
  long-context decode the KV cache shards its *sequence* dim over ``data``;
* every rule degrades axis-by-axis to replication when the dim is not
  divisible by the mesh axis (e.g. mamba2-130m's 3352-wide in_proj).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any

# weight-name classification: which dim is tensor-parallel ("model")
_UP_LIKE = {"wq", "wk", "wv", "w1", "w3", "wdq", "wuq", "wkv_a", "wkv_b",
            "in_proj", "vision_proj"}
_DOWN_LIKE = {"wo", "w2", "out_proj"}
_REPLICATED = {"ln1", "ln2", "lnx", "final_ln", "gate", "gate_norm", "A_log",
               "D", "dt_bias", "bq", "bk", "bv", "conv_b", "router"}


def _axis_size(mesh: Mesh, axis) -> int:
    """Product of the named axes' sizes; axes absent from the mesh count as
    1 (the rule then degrades via :func:`fit_spec`, which drops them)."""
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape.get(a, 1) for a in axis]))
    return mesh.shape.get(axis, 1)


def _axes_in_mesh(mesh: Mesh, axis) -> bool:
    if axis is None:
        return True
    names = mesh.axis_names
    if isinstance(axis, tuple):
        return all(a in names for a in axis)
    return axis in names


def fit_spec(mesh: Mesh, shape: tuple, spec: P) -> P:
    """Drop sharding on any dim whose size isn't divisible by its axis, and
    on any axis the mesh doesn't carry (e.g. ``param_spec`` rules applied to
    a round mesh without a ``data`` axis, or a 1-D serving mesh without
    ``model``) — every rule degrades axis-by-axis to replication."""
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        ok = _axes_in_mesh(mesh, axis) and dim % _axis_size(mesh, axis) == 0
        out.append(axis if ok else None)
    return P(*out)


def _data_axis(mesh: Mesh):
    return "data" if "data" in mesh.axis_names else None


_MOE_EXPERT_WEIGHTS = {"w1", "w3", "w2"}


def param_spec(path: tuple, shape: tuple, mesh: Mesh, mode: str = "baseline") -> P:
    """PartitionSpec for one parameter, by tree path + shape.

    Modes (hillclimb levers, EXPERIMENTS.md §Perf):
      baseline — TP over "model", FSDP over "data" (weights gather per use);
      ep       — expert-parallel: MoE expert dim sharded over "data" instead
                 of FSDP'ing the expert matrices; token movement becomes a
                 tiny all-to-all and the per-step expert-weight all-gather
                 disappears (decisive for MoE decode).
    """
    name = str(path[-1])
    da = _data_axis(mesh)

    if name in _REPLICATED or len(shape) <= 1:
        return P()
    if name == "embed":                       # [V, d]
        return fit_spec(mesh, shape, P("model", da))
    if name == "unembed":                     # [d, V]
        return fit_spec(mesh, shape, P(da, "model"))
    if name == "conv_w":                      # [n, W, C]
        return fit_spec(mesh, shape, P(None, None, "model"))

    # MoE expert weights: [n, E, in, out]
    is_expert = name in _MOE_EXPERT_WEIGHTS and len(shape) == 4
    if is_expert and mode == "ep":
        # expert dim over data (E % 16 == 0 for the assigned MoE archs),
        # ff dim over model — fully 2D-sharded, no per-use gather.
        if name == "w2":
            return fit_spec(mesh, shape, P(None, da, "model", None))
        return fit_spec(mesh, shape, P(None, da, None, "model"))

    # stacked-by-blocks weights carry a leading scan dim; MoE adds expert dim.
    lead = len(shape) - 2                     # dims before [in, out]
    prefix = (None,) * lead
    if name in _UP_LIKE:
        return fit_spec(mesh, shape, P(*prefix, da, "model"))
    if name in _DOWN_LIKE:
        return fit_spec(mesh, shape, P(*prefix, "model", da))
    return P()                                # default: replicate


def param_spec_tp(path: tuple, shape: tuple, mesh: Mesh,
                  mode: str = "baseline") -> P:
    """:func:`param_spec` with the FSDP ``"data"`` component stripped —
    tensor-parallel over ``"model"`` only, replicated elsewhere.

    For meshes whose ``"data"``-named axis is NOT a weight-sharding axis:
    serving meshes (slots over ``"data"``) and federated-round meshes
    (clients over the first axis, whatever its name).  FSDP'ing frozen
    weights there would all-gather them per use — exactly the per-step
    base gather the round path is designed to avoid."""
    def _strip_data(ax):
        if ax == "data":
            return None
        if isinstance(ax, tuple):          # keep non-"data" components
            kept = tuple(a for a in ax if a != "data")
            return kept[0] if len(kept) == 1 else (kept or None)
        return ax

    spec = param_spec(path, shape, mesh, mode)
    return fit_spec(mesh, shape, P(*[_strip_data(ax) for ax in spec]))


def lora_spec(path: tuple, shape: tuple, mesh: Mesh, mode: str = "baseline") -> P:
    """LoRA adapters replicate — they are the cross-client aggregation
    objects and tiny relative to base weights."""
    return P()


def pad_vocab(params: Pytree, multiple: int) -> Pytree:
    """``params`` with the embedding's rows zero-padded to a multiple of
    ``multiple``, so that :func:`param_spec` can split the vocabulary over
    a ``"model"`` axis of that size where the published count does not
    divide (MiniCPM's 122,753).  A tied ``transformer._unembed`` gives the
    pad its lowest logit, so the pad changes no loss, gradient or argmax;
    the lookup never reads it (token ids stay below ``vocab_size``)."""
    pad = -params["embed"].shape[0] % multiple
    if not pad:
        return params
    return dict(params, embed=jnp.pad(params["embed"], ((0, pad), (0, 0))))


def _path_names(path) -> tuple:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(p.key)
        elif hasattr(p, "name"):
            out.append(p.name)
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


def tree_param_shardings(tree: Pytree, mesh: Mesh, spec_fn=param_spec,
                         mode: str = "baseline") -> Pytree:
    """Map a pytree of arrays/ShapeDtypeStructs to NamedShardings."""

    def _one(path, leaf):
        spec = spec_fn(_path_names(path), leaf.shape, mesh, mode)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(_one, tree)


def batch_axes(mesh: Mesh):
    """Axes over which the global batch shards (pod major, then data)."""
    names = [a for a in ("pod", "data") if a in mesh.axis_names]
    return tuple(names) if names else None


def batch_spec(shape: tuple, mesh: Mesh, *, seq_axis: int | None = None) -> P:
    """Shard dim 0 (batch) over (pod, data) when divisible; otherwise, if a
    sequence axis is given (decode caches / long-context), shard that over
    data.  Degrades to replication."""
    ba = batch_axes(mesh)
    if ba is None:
        return P()
    bsz = int(np.prod([mesh.shape[a] for a in ba]))
    if shape[0] % bsz == 0 and shape[0] >= bsz:
        spec = [None] * len(shape)
        spec[0] = ba if len(ba) > 1 else ba[0]
        return P(*spec)
    if seq_axis is not None and shape[seq_axis] % mesh.shape["data"] == 0:
        spec = [None] * len(shape)
        spec[seq_axis] = "data"
        return P(*spec)
    return P()


_SEQ_CACHES = ("k", "v", "c_kv", "k_rope")


def cache_spec(path: tuple, shape: tuple, mesh: Mesh, mode: str = "baseline") -> P:
    """Decode-cache sharding: [n_blocks, B, S, ...feature dims].

    baseline — batch over (pod,data) when divisible (else sequence over
    data); trailing feature dim over "model".
    seq      — batch over (pod,data), **sequence over "model"** for KV/latent
    caches.  Feature-dim sharding puts the attention *contraction* dim on the
    mesh, which XLA undoes with a per-step cache all-gather (measured: 512 MB
    ×60 layers/step on deepseek-v2 decode — EXPERIMENTS.md §Perf H1);
    sequence sharding keeps scores local and reduces softmax/context with
    KB-sized all-reduces instead.
    """
    da = batch_axes(mesh)
    name = str(path[-1])
    spec = [None] * len(shape)
    bsz = int(np.prod([mesh.shape[a] for a in da])) if da else 1
    batch_ok = len(shape) >= 2 and da and shape[1] % bsz == 0 and shape[1] >= bsz
    if batch_ok:
        spec[1] = da if len(da) > 1 else da[0]
    if name in _SEQ_CACHES and len(shape) >= 3:
        if mode == "seq" and shape[2] % _axis_size(mesh, "model") == 0:
            spec[2] = "model"                 # sequence over model axis
        elif not batch_ok and "data" in mesh.axis_names \
                and shape[2] % mesh.shape["data"] == 0:
            spec[2] = "data"                  # long-context batch=1 fallback
    if mode != "seq" and shape[-1] % _axis_size(mesh, "model") == 0 and shape[-1] > 1:
        spec[-1] = "model"
    return fit_spec(mesh, shape, P(*spec))


def tree_cache_shardings(tree: Pytree, mesh: Mesh, mode: str = "baseline") -> Pytree:
    def _one(path, leaf):
        return NamedSharding(mesh, cache_spec(_path_names(path), leaf.shape,
                                              mesh, mode))

    return jax.tree_util.tree_map_with_path(_one, tree)


def tree_batch_shardings(tree: Pytree, mesh: Mesh) -> Pytree:
    def _one(leaf):
        return NamedSharding(mesh, batch_spec(leaf.shape, mesh))

    return jax.tree_util.tree_map(_one, tree)


def round_mesh_axes(mesh: Mesh) -> tuple:
    """Classify a federated-round mesh into ``(client_axis, model_axis)``.

    * 1-D mesh (any axis name, e.g. ``("clients",)``): the whole mesh is the
      client axis — today's pure client-parallel round;
    * 2-D mesh whose LAST axis is named ``"model"`` (e.g.
      ``("client", "model")``): sampled clients split over the first axis
      while each client group's local training runs tensor-parallel over
      ``"model"`` (the ``param_spec`` / ``cache_spec`` partition rules apply
      directly — they shard over ``"model"`` and ignore axes the mesh
      doesn't carry).

    Anything else is rejected loudly — a silent single-device fallback on a
    256-chip mesh would be an expensive no-op.
    """
    names = tuple(mesh.axis_names)
    if len(names) == 1:
        return names[0], None
    if len(names) == 2 and names[1] == "model" and names[0] != "model":
        return names[0], "model"
    raise ValueError(
        f"round mesh must be 1-D (client axis) or 2-D with axes "
        f"(client, 'model'); got axes {names}")


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def tree_replicated(tree: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree_util.tree_map(lambda _: replicated(mesh), tree)
