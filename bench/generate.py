"""The one traffic generator: every mix is a JSON file of parameters
under ``bench/traffic/`` that this module reads.

Sizes, gaps between arrivals and popularity ranks are drawn once from
the mix's own ``fixed_seed``, so every run of a mix offers the same
amount of work; ``--seed`` permutes them (an offline queue keeps its
order) and draws the tokens, the classes' order and which tenant holds
which popularity rank.  That keeps two seeds as alike in cost as two
runs of one seed.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(w) for w in words]))


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths: ``lognormal`` (``median``, ``sigma``) or
    ``uniform``, clipped to ``[min, max]``."""
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_ranks(n_ranks: int, s: float, n: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of a rank in ``[0, n_ranks)`` with P(r) ~ (r+1)^-s."""
    p = np.arange(1, n_ranks + 1, dtype=np.float64) ** -s
    return rng.choice(n_ranks, size=n, p=p / p.sum())


@dataclass
class ServeRequest:
    uid: int
    arrival: float             # seconds after the window opens
    prompt: np.ndarray         # int32 tokens
    max_new: int
    latency_class: str
    tenant: Optional[int]      # index into the tenant list; None = base


def serve_requests(traffic: dict, vocab: int, seed: int, tenant_count: int,
                   horizon: float) -> List[ServeRequest]:
    """Requests of an open-loop (``poisson``) or offline (``at_start``)
    serving mix, in arrival order.  An open-loop mix offers the requests
    due within ``horizon`` seconds: the gaps are drawn once, so every
    seed offers the same number of requests, the last at the same time."""
    fixed = _rng(traffic["fixed_seed"])
    mine = _rng(seed, 1)
    arr = traffic["arrivals"]
    if arr["process"] == "poisson":
        gaps = fixed.exponential(1.0 / arr["rate"],
                                 int(4 * arr["rate"] * horizon) + 16)
        n = max(int(np.searchsorted(np.cumsum(gaps), horizon, "right")), 1)
        arrivals = np.cumsum(gaps[:n][mine.permutation(n)])
    elif arr["process"] == "at_start":
        n = traffic["requests"]
        arrivals = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    prompts = draw_lengths(traffic["prompt"], n, fixed)
    outputs = draw_lengths(traffic["output"], n, fixed)
    if arr["process"] == "poisson":
        # an offline queue keeps the mix's own order: only its head is
        # served in a window, so a seed's order would change the work
        order = mine.permutation(n)
        prompts, outputs = prompts[order], outputs[order]

    classes = traffic["classes"]
    first = int(mine.integers(len(classes))) \
        if arr["process"] == "poisson" else 0
    tenants = _tenant_choices(traffic.get("tenants"), arrivals, tenant_count,
                              fixed, seed)
    toks = _rng(seed, 2)
    return [
        ServeRequest(
            uid=i, arrival=float(arrivals[i]),
            prompt=toks.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            max_new=int(outputs[i]),
            latency_class=classes[(first + i) % len(classes)],
            tenant=tenants[i],
        )
        for i in range(n)
    ]


def _tenant_choices(spec, arrivals, tenant_count, fixed, seed):
    """Per request: None (the base model) for a ``base_share`` of them,
    else a tenant by Zipf popularity whose rank order is reshuffled from
    the seed every ``reshuffle_s`` seconds."""
    n = len(arrivals)
    if not spec:
        return [None] * n
    base = fixed.random(n) < spec["base_share"]
    ranks = zipf_ranks(tenant_count, spec["zipf"], n, fixed)
    mine = _rng(seed, 3)
    perm = mine.permutation(n)
    base, ranks = base[perm], ranks[perm]
    epochs = (arrivals // spec["reshuffle_s"]).astype(int)
    maps = {}
    out = []
    for i in range(n):
        e = int(epochs[i])
        if e not in maps:
            maps[e] = _rng(seed, 4, e).permutation(tenant_count)
        out.append(None if base[i] else int(maps[e][ranks[i]]))
    return out


def train_rows(traffic: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """One step's ``(rows, seq_len + 1)`` int32 rows, packed from
    documents whose lengths come from the mix and whose tokens come from
    ``(seed, step)``: every step's rows differ."""
    seq, rows = traffic["seq_len"], traffic["step_tokens"] // traffic["seq_len"]
    need = rows * (seq + 1)
    fixed = _rng(traffic["fixed_seed"], step)
    lengths = []
    while sum(lengths) < need:
        lengths.extend(draw_lengths(traffic["document"], 64, fixed).tolist())
    toks = _rng(seed, 5, step)
    stream = np.concatenate([toks.integers(0, vocab, n, dtype=np.int32)
                             for n in lengths])
    return stream[:need].reshape(rows, seq + 1)
