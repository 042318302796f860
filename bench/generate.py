"""The one traffic generator: turns a traffic file (`traffic/<mix>.json`)
and a run's seed into the requests a cell serves.

Every seed gets the same multiset of request sizes and the same arrival
schedule; the seed only chooses the order in which sizes meet arrival
times, and the contents (latents, conditioning, token ids). So runs with
different seeds do the same amount of work, and a seed never changes
what is measured. The sizes and arrival times come from the traffic
file's own `shape_seed`.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class LMRequest:
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due_s: Optional[float]      # open loop: due time from window start


@dataclasses.dataclass
class DiTRequest:
    num_steps: int
    t_start: float
    latent: np.ndarray          # (seq_len, patch_dim) float32
    cond: Optional[int]         # index into the conditioning pool


def _lognormal_clipped(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def lm_sizes(traffic: dict) -> np.ndarray:
    """(pool, 2) prompt and output lengths: the fixed multiset of the mix,
    drawn once from the traffic file's shape_seed, kinds interleaved."""
    rng = np.random.default_rng(traffic["shape_seed"])
    pool = int(traffic["pool"])
    kinds = traffic["mix"]
    counts = [int(round(k["share"] * pool)) for k in kinds]
    counts[-1] = pool - sum(counts[:-1])
    rows = []
    for kind, n in zip(kinds, counts):
        rows.append(np.stack([_lognormal_clipped(rng, kind["prompt"], n),
                              _lognormal_clipped(rng, kind["output"], n)],
                             axis=1))
    sizes = np.concatenate(rows)
    return sizes[rng.permutation(len(sizes))]


def arrival_times(traffic: dict, seconds: float) -> np.ndarray:
    """Open-loop due times in [0, seconds): a Poisson process at the
    traffic's fixed rate, drawn from shape_seed (the same for every run
    seed). Empty for a backlog."""
    if traffic["loop"] != "open":
        return np.zeros((0,))
    rng = np.random.default_rng([traffic["shape_seed"], 1])
    rate = float(traffic["rate_per_s"])
    t, out = 0.0, []
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= seconds:
            return np.asarray(out)
        out.append(t)


def lm_requests(traffic: dict, seed: int, vocab: int,
                seconds: float) -> List[LMRequest]:
    """The run's requests, in submission order. A backlog submits the
    whole pool at once (due_s None), the seed shuffling it only within
    consecutive groups of `order_block` requests, so that every seed's
    window serves the same groups; an open loop gives each arrival time
    of the window one request from the pool."""
    sizes = lm_sizes(traffic)
    rng = np.random.default_rng(seed)
    due = arrival_times(traffic, seconds)
    if traffic["loop"] == "open":
        if len(due) > len(sizes):
            raise ValueError(f"traffic pool {len(sizes)} is smaller than "
                             f"the {len(due)} arrivals of the window")
        sizes = sizes[:len(due)]
        sizes = sizes[rng.permutation(len(sizes))]
    else:
        blk = int(traffic["order_block"])
        order = np.concatenate([i + rng.permutation(min(blk, len(sizes) - i))
                                for i in range(0, len(sizes), blk)])
        sizes = sizes[order]
    out = []
    for i, (plen, onew) in enumerate(sizes):
        prompt = rng.integers(0, vocab, size=int(plen)).astype(np.int32)
        out.append(LMRequest(prompt=prompt, max_new=int(onew),
                             due_s=float(due[i]) if len(due) else None))
    return out


def dit_requests(traffic: dict, seed: int, patch_dim: int):
    """(fill, backlog): the requests that occupy the slots when the
    window opens — slot j's first request runs `fill_steps[j]` steps at
    the mix's step size, so the slots turn over staggered — and the
    standing queue of full requests behind them."""
    rng = np.random.default_rng(seed)
    n, t_start = int(traffic["num_steps"]), float(traffic["t_start"])
    shape = (int(traffic["seq_len"]), patch_dim)
    pool = int(traffic["cond_pool"])

    def req(steps, i):
        return DiTRequest(num_steps=steps, t_start=t_start * steps / n,
                          latent=rng.standard_normal(shape, np.float32),
                          cond=i % pool)

    fill = [req(int(s), j) for j, s in enumerate(traffic["fill_steps"])]
    backlog = [req(n, j + len(fill)) for j in range(int(traffic["backlog"]))]
    return fill, backlog


def dit_conds(traffic: dict, seed: int, cond_len: int,
              d_model: int) -> np.ndarray:
    """(cond_pool, cond_len, d_model) seeded text-conditioning stand-ins."""
    rng = np.random.default_rng([seed, 2])
    return rng.standard_normal((int(traffic["cond_pool"]), cond_len,
                                d_model), np.float32)
