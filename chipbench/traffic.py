"""The one generator: turns a traffic file (``chipbench/traffic/<mix>.json``)
and a seed into the inputs of a run.

Every seed gets the same amount of work.  Federated shards: the seed
reorders a fixed set of sizes and draws the tokens.  Serving: the sizes,
gaps and tenant popularities are one fixed set of quantiles, dealt out in
blocks of ``STRATA`` consecutive requests that each hold one value from
each of ``STRATA`` equal bands of each law.  Prompt and output lengths
and arrival gaps keep one interleaved order for every seed: above
capacity the engine's throughput follows the order in which lengths meet
its slots, so an order drawn from the seed would change the work.  The
seed orders the tenant popularities within that frame and draws which
tenant is popular, the prompt tokens, the weights and the adapters.  A
serving ``backlog`` keeps the queue from running dry at any admission in
the window, so which admissions share a prefill burst follows the
engine's own step counts and not the moment the host read its clock.

* ``kind: "fedround"`` — per-client training shards of fixed-length
  sequences ``[BOS] prompt [SEP] answer`` with the loss on the answer
  span; a ``missing_text`` share of each shard has its prompt replaced by
  PAD (the FedMultimodal missing-text protocol the paper follows).
* ``kind: "serve"`` — an open-loop request list: a ``backlog`` of
  requests queued before the window, then ``round(rate·seconds)``
  requests whose inter-arrival gaps sit at fixed quantiles of an
  exponential law (Poisson arrivals), lognormal prompt and output lengths
  at fixed quantiles, tenants at fixed quantiles of a Zipf law; and the
  adapter bank's history before the window, from the same Zipf law.
"""

from __future__ import annotations

import math
import os
from statistics import NormalDist

import numpy as np

from chipbench.model import HERE, read_json

PAD, BOS, SEP = 0, 1, 3
FIRST_TOKEN = 4
STRATA = 8
GOLDEN = (math.sqrt(5) - 1) / 2


def traffic_path(name: str, root: str = HERE) -> str:
    return os.path.join(root, "traffic", f"{name}.json")


def load_traffic(name: str, root: str = HERE) -> dict:
    return read_json(traffic_path(name, root))


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


# ---------------------------------------------------------------- fedround

def fedround_shards(mix: dict, vocab: int, seed: int) -> list[dict]:
    """One training shard per client: tokens, labels, loss_mask."""
    S, P = mix["seq_len"], mix["prompt_len"]
    sizes = _rng(seed, 1).permutation(np.asarray(mix["examples_per_client"]))
    rng = _rng(seed, 2)
    shards = []
    for n in sizes:
        toks = rng.integers(FIRST_TOKEN, vocab, (n, S + 1), dtype=np.int32)
        toks[:, 0] = BOS
        toks[:, P + 1] = SEP
        miss = rng.random(n) < mix["missing_text"]
        toks[miss, 1:P + 1] = PAD
        mask = np.zeros((n, S), np.float32)
        mask[:, P + 1:] = 1.0          # labels past SEP: the answer span
        shards.append({"tokens": toks[:, :S], "labels": toks[:, 1:],
                       "loss_mask": mask})
    return shards


# ---------------------------------------------------------------- serve

def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 1/2)/n of a lognormal with the given
    median and sigma, clipped to [min, max]."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _zipf_quantiles(a: float, n_tenants: int, n: int) -> np.ndarray:
    """n tenant indices at the quantiles (i + 1/2)/n of Zipf(a) over
    ``n_tenants`` (index 0 the most popular)."""
    w = 1.0 / np.arange(1, n_tenants + 1) ** a
    cdf = np.cumsum(w / w.sum())
    q = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, q), n_tenants - 1)


def _stratified(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` in a seeded order in which block b (positions
    ``STRATA·b`` …) takes the b-th of a seeded shuffle of each of
    ``STRATA`` bands of the sorted values, its members shuffled too."""
    bands = [rng.permutation(b) for b in
             np.array_split(np.sort(values), STRATA)]
    blocks = [[b[i] for b in bands if i < len(b)]
              for i in range(len(bands[0]))]
    return np.concatenate([rng.permutation(np.asarray(b)) for b in blocks])


def _interleaved(values: np.ndarray, step: int) -> np.ndarray:
    """``values`` in one fixed order: block b (positions ``STRATA·b`` …)
    takes one value of each of ``STRATA`` bands of the sorted values, each
    band's members in golden-ratio order so that every prefix of the
    schedule spans each band evenly; in block b band j sits at position
    ``(step·j + b) mod STRATA`` (``step`` odd), so laws given different
    steps pair their bands differently."""
    bands = np.array_split(np.sort(values), STRATA)
    order = [np.argsort((np.arange(len(b)) * GOLDEN) % 1.0) for b in bands]
    blocks = []
    for i in range(len(bands[0])):
        here = [j for j, b in enumerate(bands) if i < len(b)]
        here.sort(key=lambda j: (step * j + i) % STRATA)
        blocks.append([bands[j][order[j][i]] for j in here])
    return np.concatenate(blocks)


def tenant_names(mix: dict, seed: int) -> np.ndarray:
    """Which tenant holds each popularity rank (0 the most popular)."""
    return _rng(seed, 3).permutation(mix["tenants"])


def serve_requests(mix: dict, vocab: int, seed: int,
                   seconds: float) -> list[dict]:
    """The window's requests, sorted by due time (seconds from its start):
    ``{"due", "tenant", "prompt" (int32 tokens), "gen_len"}``.  The first
    ``backlog`` (default 0) arrived before the window and are due at its
    start; ``round(rate·seconds)`` more arrive in it."""
    b = mix.get("backlog", 0)
    n = max(int(round(mix["rate"] * seconds)), 1)
    plens = _interleaved(_lognormal_quantiles(mix["prompt"], b + n), 1)
    glens = _interleaved(_lognormal_quantiles(mix["output"], b + n), 3)
    ranks = _stratified(_zipf_quantiles(mix["zipf_a"], mix["tenants"], b + n),
                        _rng(seed, 4))
    tenants = tenant_names(mix, seed)[ranks]
    # inter-arrival gaps at fixed quantiles of the exponential law, scaled
    # so the last request falls due inside the window
    q = (np.arange(n) + 0.5) / n
    gaps = _interleaved(-np.log1p(-q), 5)
    due = np.concatenate([np.zeros(b), [0.0], np.cumsum(gaps)[:-1]
                          * seconds / gaps.sum()])
    tok = _rng(seed, 5)
    return [{"due": float(due[i]), "tenant": int(tenants[i]),
             "prompt": tok.integers(FIRST_TOKEN, vocab, plens[i],
                                    dtype=np.int32),
             "gen_len": int(glens[i])} for i in range(b + n)]


def bank_history(mix: dict, seed: int) -> list[int]:
    """The tenants a server that ran before the window served last, oldest
    first: the window's Zipf law, drawn until ``bank_slots`` distinct
    tenants are in it, so the bank starts the window full."""
    law = _zipf_quantiles(mix["zipf_a"], mix["tenants"], mix["tenants"])
    ranks = _stratified(law, _rng(seed, 7))
    names, seen, out = tenant_names(mix, seed), set(), []
    for r in ranks:
        out.append(int(names[r]))
        seen.add(int(r))
        if len(seen) == mix["bank_slots"]:
            break
    return out


def warm_requests(mix: dict, vocab: int, seed: int) -> list[dict]:
    """Set-up traffic: two requests, one prompt inside a prefill chunk and
    one across two, on two tenants.  Every program the window runs has one
    shape whatever the lengths (admission, chunked prefill, decode, the
    adapter page-in scatter); the completion fetch is warmed apart."""
    tok = _rng(seed, 6)
    lens = [mix["prompt"]["min"], mix["prefill_chunk"] + 1]
    return [{"due": 0.0, "tenant": i, "gen_len": mix["output"]["min"],
             "prompt": tok.integers(FIRST_TOKEN, vocab, n, dtype=np.int32)}
            for i, n in enumerate(lens)]
