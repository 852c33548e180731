"""Compile a cell's programs at its real size for a described TPU, without
the chip, and print what the compiler says of their memory.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell> \
        [--batch 1 2 4 8] [--topology v5e:2x2] [--mesh 2 2]

Federated cells: the fused ``round_step`` at each per-client batch given
(the cell's own when none is), on one chip of the topology or, with
``--mesh``, on a (client, model) mesh of its chips.  Serving cells: the
decode, chunked-prefill and admission programs.  One JSON line per
program: ``memory_analysis()`` in bytes and the compile seconds.  Nothing
runs, so nothing here is a measurement of time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}
    out["total_bytes"] = (out.get("argument_size_in_bytes", 0)
                          + out.get("output_size_in_bytes", 0)
                          + out.get("temp_size_in_bytes", 0)
                          - out.get("alias_size_in_bytes", 0))
    return out


def _sds(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def fedround(raw, mix, batch: int, devices, mesh_shape):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench import model
    from repro import sharding as SH
    from repro.core.editing import EditConfig
    from repro.core.lora import LoRAConfig, init_lora_params
    from repro.launch.fedround import make_round_engine
    from repro.models import transformer as T
    from repro.optim import OptimizerConfig

    dm = model.dims(raw)
    mcfg = model.model_config(raw, "rehearse")
    r_g = max(mix["ranks"])
    K = mix["num_clients"]
    n_s = max(int(round(mix["sample_rate"] * K)), 1)
    specs = T.lora_specs(mcfg)
    mesh = None
    if mesh_shape:
        from jax.sharding import AxisType, Mesh
        import numpy as np
        mesh = Mesh(np.asarray(devices[:mesh_shape[0] * mesh_shape[1]])
                    .reshape(mesh_shape), ("client", "model"),
                    axis_types=(AxisType.Auto,) * 2)
        rep = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P("client") if K % mesh_shape[0] == 0
                             else P())
        base_shapes = model.weight_shapes(dm)
        shard = SH.tree_param_shardings(base_shapes, mesh,
                                        spec_fn=SH.param_spec_tp)
        base = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            base_shapes, shard)
    else:
        from jax.sharding import SingleDeviceSharding
        rep = rows = SingleDeviceSharding(devices[0])
        base = _sds(model.weight_shapes(dm), rep)
    step = make_round_engine(
        mcfg, OptimizerConfig(peak_lr=mix["lr"]), specs=specs,
        lora_scale=mix["lora_alpha"] / r_g, r_g=r_g,
        edit=EditConfig(enabled=mix["edit"]), aggregator=mix["aggregator"],
        mesh=mesh, n_sample=n_s)
    g = jax.eval_shape(lambda: init_lora_params(
        jax.random.PRNGKey(0), specs, LoRAConfig(rank=r_g)))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((K,) + x.shape, x.dtype, sharding=rows),
        g)
    n_max = max(mix["examples_per_client"])
    S = mix["seq_len"]
    data = {"tokens": jax.ShapeDtypeStruct((K, n_max, S), jnp.int32,
                                           sharding=rows),
            "labels": jax.ShapeDtypeStruct((K, n_max, S), jnp.int32,
                                           sharding=rows),
            "loss_mask": jax.ShapeDtypeStruct((K, n_max, S), jnp.float32,
                                              sharding=rows)}
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
    args = (base, stacked, _sds(g, rep), _sds(g, rep), i32((K,)),
            jax.ShapeDtypeStruct((K,), jnp.float32, sharding=rep), data,
            i32((n_s,)), i32((n_s,)), i32((n_s, mix["local_steps"], batch)),
            i32(()))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(1, 2, 3, 4)).lower(*args) \
        .compile()
    return {"program": "round_step", "batch_per_client": batch,
            "mesh": list(mesh_shape or ()), "compile_s":
            time.perf_counter() - t0, **_mem(compiled),
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}


def serve(raw, mix, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chipbench import model
    from repro.launch.steps import (make_chunked_prefill_step,
                                    make_multi_adapter_serve_step)
    from repro.models import transformer as T

    dm = model.dims(raw)
    mcfg = model.model_config(raw, "rehearse")
    one = SingleDeviceSharding(devices[0])
    base = _sds(model.weight_shapes(dm), one)
    r_g = max(mix["ranks"])
    B, G = mix["max_slots"], mix["bank_slots"]
    Sp, Sg = mix["prompt"]["max"], mix["output"]["max"]
    bank = {}
    for name, (din, dout) in model.lora_sites(dm).items():
        bank[name] = {"A": jax.ShapeDtypeStruct((dm["layers"], G, r_g, din),
                                                jnp.float32, sharding=one),
                      "B": jax.ShapeDtypeStruct((dm["layers"], G, dout, r_g),
                                                jnp.float32, sharding=one)}
    cache = _sds(jax.eval_shape(lambda: T.init_cache(mcfg, None, B, Sp + Sg)),
                 one)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    state = {"ptoks": i32(B, Sp), "aidx": i32(B), "pos": i32(B),
             "plen": i32(B), "tlen": i32(B), "last": i32(B), "gen": i32(B, Sg),
             "fault": jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)}
    scale = mix["lora_alpha"] / r_g
    out = []
    prefill = make_chunked_prefill_step(
        mcfg, lora_scale=scale, chunk=mix["prefill_chunk"],
        lora_backend=mix["lora_backend"], bank_layout="scan")
    serve_fn = make_multi_adapter_serve_step(
        mcfg, lora_scale=scale, lora_backend=mix["lora_backend"],
        bank_layout="scan")
    embeds = jax.ShapeDtypeStruct((B, dm["d"]), jnp.dtype(dm["dtype"]),
                                  sharding=one)
    for name, fn, args in (
            ("prefill_step", prefill, (base, bank, state, cache)),
            ("serve_step", serve_fn,
             (base, bank, i32(B), cache, embeds, i32(B)))):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        out.append({"program": name, "compile_s": time.perf_counter() - t0,
                    **_mem(compiled), "tpu_custom_calls":
                    compiled.as_text().count("tpu_custom_call")})
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, nargs="*")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--mesh", type=int, nargs=2)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from jax.experimental import topologies

    from chipbench import harness, model, traffic
    from repro.kernels import ops

    ops._interpret = lambda interpret=None: False    # lower to Mosaic
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.find_cell(harness.load_bench(), args.workload)
    raw = model.load_config(cell["config"])
    mix = traffic.load_traffic(cell["traffic"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    if mix["kind"] == "fedround":
        for b in args.batch or [mix["batch_per_client"]]:
            try:
                r = fedround(raw, mix, b, topo.devices, args.mesh)
            except jax.errors.JaxRuntimeError as e:    # does not fit
                r = {"program": "round_step", "batch_per_client": b,
                     "error": str(e).splitlines()[0][:300]}
            print(json.dumps(dict(r, workload=args.workload)), flush=True)
    else:
        for r in serve(raw, mix, topo.devices):
            print(json.dumps(dict(r, workload=args.workload)), flush=True)


if __name__ == "__main__":
    main()
