"""Window arithmetic: rates over the measured window and tails over all
requests of the window. Kept apart from the runners so that tests can
check it on made-up timelines."""
from __future__ import annotations

import math
from typing import Dict, List, Sequence


def nearest_rank(xs: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: sorted(xs)[ceil(p * n) - 1] (the serving
    convention of the program's `serving.api.percentile`, copied so the
    yardstick stays fixed)."""
    xs = sorted(xs)
    if not xs:
        raise ValueError("percentile of an empty sequence")
    rank = min(len(xs), max(1, math.ceil(p * len(xs))))
    return xs[rank - 1]


def median(xs: Sequence[float]) -> float:
    xs = sorted(xs)
    if not xs:
        raise ValueError("median of an empty sequence")
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def rate(work: float, t0: float, t_end: float) -> float:
    """Work per second over the whole window."""
    if t_end <= t0:
        raise ValueError("empty window")
    return work / (t_end - t0)


def ttfts_and_gaps(requests: List[Dict]) -> tuple:
    """Each request of the window: {"due": t, "tokens": [t1, t2, ...]}
    (harness clock, seconds). Returns (ttfts, gaps) over ALL of them:
    time to first token from the due time, and every gap between
    consecutive tokens. A request of the window that never produced a
    token is an error, not a missing sample."""
    ttfts, gaps = [], []
    for r in requests:
        toks = r["tokens"]
        if not toks:
            raise ValueError(f"request due at {r['due']} has no tokens")
        ttfts.append(toks[0] - r["due"])
        gaps.extend(b - a for a, b in zip(toks, toks[1:]))
    return ttfts, gaps
