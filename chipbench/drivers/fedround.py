"""Federated rounds: ``FederatedTrainer.run_round``, one fused ``round_step``
dispatch per round, ending in its metrics fetch.

Set-up builds ONE trainer over the benchmark's base weights and initial
global adapter, and drives it from the seed through the checked rounds
(the first compiles ``round_step``).  The window then calls ``run_round``
on that same trainer until ``seconds`` have passed.  The check replays the
checked rounds in the plain reference and compares, per round, the loss,
and, by the worst leaf, the norm of each round-1 client's update and of
the global adapter's change over the checked rounds.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, model, reference, traffic


def _leaf_norms(tree) -> dict:
    return {f"{n}/{m}": float(np.linalg.norm(np.asarray(e[m], np.float64)))
            for n, e in tree.items() for m in ("A", "B")}


def _diff(a, b):
    return {n: {m: np.asarray(a[n][m], np.float64) - np.asarray(b[n][m],
                                                                np.float64)
                for m in ("A", "B")} for n in a}


def _rel(diff, base) -> float:
    """‖diff‖ / ‖base‖ over every leaf of two difference trees."""
    sq = lambda t: sum(float(np.sum(e[m] ** 2)) for e in t.values()
                       for m in ("A", "B"))
    return math.sqrt(sq(diff) / max(sq(base), 1e-30))


def norm_gap(prog: dict, ref: dict) -> float:
    """Worst leaf of |‖prog‖ − ‖ref‖| over max(‖ref leaf‖, median leaf)."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in ref)


def truncate(tree, rank: int, r_g: int):
    m = (np.arange(r_g) < rank).astype(np.float32)
    return {n: {"A": np.asarray(e["A"]) * m[None, :, None],
                "B": np.asarray(e["B"]) * m[None, None, :]}
            for n, e in tree.items()}


class Run:
    def __init__(self, raw: dict, mix: dict, seed: int, chips: int, *,
                 traced: bool = False):
        self.raw, self.mix, self.seed, self.chips = raw, mix, seed, chips
        self.dm = model.dims(raw)
        self.traced = traced
        r_g = max(mix["ranks"])
        self.hp = {"r_g": r_g, "lr": mix["lr"],
                   "scale": mix["lora_alpha"] / r_g,
                   "n_sample": max(int(round(mix["sample_rate"]
                                             * mix["num_clients"])), 1),
                   "batch": mix["batch_per_client"],
                   "steps": mix["local_steps"], "ranks": mix["ranks"],
                   "edit": mix["edit"]}
        self.rounds = 0

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core.editing import EditConfig
        from repro.federated import FederatedConfig, FederatedTrainer
        from repro.optim import OptimizerConfig
        from repro.telemetry import Telemetry

        mix, dm, hp = self.mix, self.dm, self.hp
        t0 = time.perf_counter()
        self.base = jax.block_until_ready(model.init_weights(self.seed, dm))
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
            model.model_config(self.raw, "bench"), fcfg, ocfg, self.shards,
            [empty] * len(self.shards), empty, base_params=self.base,
            seed=self.seed, telemetry=tel)
        if tr.lora_scale != hp["scale"]:
            raise RuntimeError(f"program's LoRA scale {tr.lora_scale} is "
                               f"not the configuration's {hp['scale']}")
        tr.server.global_lora = jax.tree_util.tree_map(jnp.asarray, self.g0)
        tr.server.prev_global = jax.tree_util.tree_map(jnp.asarray, self.g0)
        t2 = time.perf_counter()
        # the checked rounds: the first compiles round_step
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

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> dict:
        tr = self.trainer
        losses = []
        t0 = time.perf_counter()
        t_end = t0
        while t_end - t0 < seconds:
            rec = tr.run_round()              # ends in its metrics fetch
            t_end = time.perf_counter()
            losses.append(rec["train_loss"])
        self.rounds = len(losses)
        self.window_s = t_end - t0
        bad = sum(not math.isfinite(x) for x in losses)
        return {"attempted": len(losses), "failed": bad,
                "metrics": {"round_s": (self.window_s / len(losses), "s")}}

    def layer_info(self) -> dict:
        """What the per-layer readers need besides the trace."""
        mix = self.mix
        ranks = [mix["ranks"][k] for k in self.prog["cohorts"][-1]]
        n_s = self.hp["n_sample"]
        return {"kind": "fedround", "rounds": self.rounds,
                "hlo_texts": [self._round_hlo()] if self.traced else [],
                "round_flops": flops.train_round(
                    self.dm, clients=n_s, steps=mix["local_steps"],
                    batch=mix["batch_per_client"], seq=mix["seq_len"],
                    ranks=ranks),
                "dim_agg_calls": flops.dim_agg_round(
                    self.dm, cohort=n_s, r_g=self.hp["r_g"])}

    def _round_hlo(self) -> str:
        """Compiled text of the trainer's ``round_step`` (a cache hit): its
        HLO metadata names the scope of each op in the trace."""
        tr, n_s, mix = self.trainer, self.hp["n_sample"], self.mix
        idx = jnp.zeros((n_s,), jnp.int32)
        return tr._get_round_step().lower(
            tr.base_params, tr.stacked_lora, tr.server.global_lora,
            tr.server.prev_global, tr._ranks_dev, tr._sizes_dev,
            tr._stacked_data, idx, idx,
            jnp.zeros((n_s, mix["local_steps"], mix["batch_per_client"]),
                      jnp.int32), jnp.zeros((), jnp.int32)).compile().as_text()

    def free(self) -> None:
        del self.trainer

    # ------------------------------------------------------------ check
    def reference(self, prec: str = "f32", half: bool = False) -> dict:
        """The checked rounds in the plain reference (``prec``; ``half``
        plants the half-batch fault)."""
        out = reference.fed_rounds(self.dm, self.hp, self.base, self.shards,
                                   self.g0, self.seed,
                                   self.mix["checked_rounds"], prec, half)
        return dict(out, global_=out["globals"][-1])

    def program(self) -> dict:
        return dict(self.prog, global_=self.prog["global"])

    def compare(self, got: dict, ref: dict) -> dict:
        """The numbers that can be compared with the reference: per round,
        the relative gap of the loss (``loss``: the worst round,
        ``loss_r1``: round 1); for each round-1 client's update (post-edit
        adapter minus its start), the gap of leaf norms over max(reference
        leaf norm, median leaf norm), by the worst leaf (``update_norm``)
        and by the median leaf (``update_median``); the norm of the
        update's difference from the reference's over the reference's, the
        worst client's (``update_diff``); per leaf, the norm of that
        difference over max(reference leaf norm, median leaf norm), the
        median over every client's leaves (``update_leaf_diff``); for the
        global adapter's change over the checked rounds, the worst leaf's
        norm gap (``change_norm``) and the norm of the difference of the
        final globals over that of the reference's change
        (``change_diff``)."""
        hp = self.hp
        if [list(c) for c in got["cohorts"]] != [list(c) for c in
                                                 ref["cohorts"]]:
            raise RuntimeError(f"cohorts differ from the protocol's: "
                               f"{got['cohorts']} against {ref['cohorts']}")
        gaps = [abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])]
        upd = {"update_norm": 0.0, "update_median": 0.0, "update_diff": 0.0}
        leaf_diffs = []
        for k, lo in ref["clients"].items():
            start = truncate(self.g0, hp["ranks"][k], hp["r_g"])
            dp, dr = _diff(got["clients"][k], start), _diff(lo, start)
            pn, rn = _leaf_norms(dp), _leaf_norms(dr)
            dn = _leaf_norms(_diff(dp, dr))
            med = float(np.median(list(rn.values())))
            leaf = [abs(pn[n] - rn[n]) / max(rn[n], med, 1e-30) for n in rn]
            leaf_diffs += [dn[n] / max(rn[n], med, 1e-30) for n in rn]
            upd["update_norm"] = max(upd["update_norm"], max(leaf))
            upd["update_median"] = max(upd["update_median"],
                                       float(np.median(leaf)))
            upd["update_diff"] = max(upd["update_diff"],
                                     _rel(_diff(dp, dr), dr))
        ref_change = _diff(ref["global_"], self.g0)
        change = norm_gap(_leaf_norms(_diff(got["global_"], self.g0)),
                          _leaf_norms(ref_change))
        return {"loss": max(gaps), "loss_r1": gaps[0], **upd,
                "update_leaf_diff": float(np.median(leaf_diffs)),
                "change_norm": change,
                "change_diff": _rel(_diff(got["global_"], ref["global_"]),
                                    ref_change)}

    def check(self) -> dict:
        ref = self.reference()
        self.diagnostics.update(edited_program=self.prog["edited"],
                                edited_reference=ref["edited"])
        return self.compare(self.program(), ref)
