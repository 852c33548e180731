"""Federated rounds on a (client, model) mesh of chips, for a model with
MiniCPM's µP scalings: ``FederatedTrainer.run_round`` with ``mesh=``, one
fused ``round_step`` dispatch per round, ending in its metrics fetch.

The traffic's ``mesh`` is ``[clients, model]``: the sampled clients split
over the first axis, each client's local training tensor-parallel over the
second.  The base weights are made on the device already split as the
trainer keeps them.  The program's configuration is ``model.model_config``
with the file's ``scale_emb``, ``scale_depth`` and ``dim_model_base``; the
check replays the checked rounds in ``reference_minicpm``, spread over the
cell's chips one client to a chip.  The window, the comparison and the
per-layer information are ``drivers/fedround.py``'s; traced runs add the
round program's collectives, counted from its compiled text.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from chipbench import model, reference_minicpm, traffic
from chipbench.drivers import fedround

MUP = ("scale_emb", "scale_depth", "dim_model_base")


class Run(fedround.Run):
    def __init__(self, raw: dict, mix: dict, seed: int, chips: int, *,
                 traced: bool = False):
        super().__init__(raw, mix, seed, chips, traced=traced)
        self.dm = dict(self.dm, **{k: raw[k] for k in MUP})
        n_client, n_model = mix["mesh"]
        if n_client * n_model != chips:
            raise ValueError(f"mesh {mix['mesh']} is not the cell's "
                             f"{chips} chips")
        # here, before any work: a program without the µP fields fails
        self.cfg = dataclasses.replace(model.model_config(raw, "bench"),
                                       **{k: raw[k] for k in MUP})

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro import sharding as SH
        from repro.core.editing import EditConfig
        from repro.federated import FederatedConfig, FederatedTrainer
        from repro.launch.mesh import make_round_mesh
        from repro.optim import OptimizerConfig
        from repro.telemetry import Telemetry

        mix, dm, hp = self.mix, self.dm, self.hp
        t0 = time.perf_counter()
        self.mesh = make_round_mesh(*mix["mesh"])
        shardings = SH.tree_param_shardings(model.weight_shapes(dm),
                                            self.mesh,
                                            spec_fn=SH.param_spec_tp)
        self.base = jax.block_until_ready(
            model.init_weights(self.seed, dm, shardings))
        t1 = time.perf_counter()
        g0 = model.init_adapters(self.seed, dm, [hp["r_g"]], hp["r_g"],
                                 mix["init_b_std"])
        self.g0 = jax.device_get(jax.tree_util.tree_map(lambda x: x[0], g0))
        self.shards = traffic.fedround_shards(mix, dm["vocab"], self.seed)
        fcfg = FederatedConfig(
            num_clients=mix["num_clients"], sample_rate=mix["sample_rate"],
            ranks=tuple(mix["ranks"]), local_steps=mix["local_steps"],
            batch_size=mix["batch_per_client"], aggregator=mix["aggregator"],
            missing_ratio=mix["missing_text"],
            edit=EditConfig(enabled=mix["edit"]), seed=self.seed,
            lora_alpha=mix["lora_alpha"])
        ocfg = OptimizerConfig(peak_lr=mix["lr"])
        tel = Telemetry(enabled=True, annotate=True) if self.traced else None
        empty = {k: v[:1] for k, v in self.shards[0].items()}
        self.trainer = tr = FederatedTrainer(
            self.cfg, fcfg, ocfg, self.shards,
            [empty] * len(self.shards), empty, base_params=self.base,
            seed=self.seed, telemetry=tel, mesh=self.mesh)
        if tr.lora_scale != hp["scale"]:
            raise RuntimeError(f"program's LoRA scale {tr.lora_scale} is "
                               f"not the configuration's {hp['scale']}")
        tr.server.global_lora = jax.tree_util.tree_map(jnp.asarray, self.g0)
        tr.server.prev_global = jax.tree_util.tree_map(jnp.asarray, self.g0)
        t2 = time.perf_counter()
        # the checked rounds: the first places the state on the mesh and
        # compiles round_step
        self.prog = {"loss": [], "cohorts": [], "clients": {}, "edited": []}
        for r in range(mix["checked_rounds"]):
            rec = tr.run_round()
            self.prog["loss"].append(rec["train_loss"])
            self.prog["cohorts"].append(rec["sampled"])
            self.prog["edited"].append(rec["edited_layers"])
            if r == 0:
                ids = jnp.asarray(rec["sampled"])
                rows = jax.device_get(jax.tree_util.tree_map(
                    lambda x: x[ids], tr.stacked_lora))
                for i, k in enumerate(rec["sampled"]):
                    self.prog["clients"][k] = jax.tree_util.tree_map(
                        lambda x: x[i], rows)
        self.prog["global"] = jax.device_get(tr.server.global_lora)
        self.diagnostics = {"setup_phases_s": {
            "weights": t1 - t0, "trainer": t2 - t1,
            "checked_rounds": time.perf_counter() - t2}}

    def layer_info(self) -> dict:
        """``fedround.Run.layer_info``; a traced run also records, in its
        diagnostics, the collectives of the compiled round program by kind:
        their count and result bytes in the program's text, where the body
        of a loop counts once."""
        info = super().layer_info()
        if info["hlo_texts"]:
            from repro.launch.hlo_analysis import collective_bytes

            coll = collective_bytes(info["hlo_texts"][0])
            self.diagnostics["round_hlo_collectives"] = {
                "count": coll["counts"], "bytes": coll["per_op"]}
        return info

    # ------------------------------------------------------------ check
    def reference(self, prec: str = "f32", half: bool = False) -> dict:
        out = reference_minicpm.fed_rounds(
            self.dm, self.hp, self.base, self.shards, self.g0, self.seed,
            self.mix["checked_rounds"], prec, half,
            devices=jax.devices()[:self.chips])
        return dict(out, global_=out["globals"][-1])
