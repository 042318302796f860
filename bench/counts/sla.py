"""SLA attention FLOPs (paper Eq. 2-6), copied from the program's
`core/flops.py` (`sla_flops`, `sla_decode_flops`) so the yardstick stays
fixed; bench/tests checks the copies against the originals. Counts are
per batch element and layer, summed over heads, forward only."""
from __future__ import annotations


def num_critical(tn: int, kh_frac: float, fixed_budget=None) -> int:
    if fixed_budget is not None:
        return max(1, min(fixed_budget, tn))
    return max(1, round(kh_frac * tn))


def sla_flops(n: int, d: int, h: int, sla: dict) -> dict:
    """sparse: 4 n^2 d x critical fraction; linear: h_j/z_j and
    phi(Q)H (Eq. 5); mask: pooled score map; aggregate: A @ h; proj:
    the d x d Proj of Eq. 6."""
    tm, tn = n // sla["block_q"], n // sla["block_kv"]
    crit_frac = num_critical(tn, sla["kh_frac"]) / tn
    sparse = 4.0 * n * n * d * crit_frac * h
    linear = 4.0 * n * d * d * h
    mask = (2.0 * tm * tn * d + 5.0 * tm * tn) * h
    agg = 2.0 * tm * tn * (d * d + d) * h
    proj = 2.0 * n * d * d * h
    return {"sparse": sparse, "linear": linear, "mask": mask,
            "aggregate": agg, "proj": proj,
            "total": sparse + linear + mask + agg + proj}


def sla_decode_flops(n: int, d: int, h: int, sla: dict,
                     num_crit=None) -> dict:
    """Per decoded token: sparse over the K critical blocks, the O(1)
    running-state update, the subtractive linear branch, Proj, and the
    block-boundary row classification amortized over b_q tokens."""
    tn = max(1, n // sla["block_kv"])
    if num_crit is not None:
        k_sel = num_crit
    elif sla.get("decode_budget") is not None:
        k_sel = sla["decode_budget"]
    else:
        k_sel = num_critical(tn, sla["kh_frac"])
    k_sel = max(1, min(k_sel, tn))
    sparse = 4.0 * k_sel * sla["block_kv"] * d * h
    state = 4.0 * d * d * h
    linear = (2.0 * k_sel * d * d + 2.0 * d * d + 2.0 * d) * h
    proj = 2.0 * d * d * h
    plan = (2.0 * tn * d + 5.0 * tn) * h / sla["block_q"]
    return {"sparse": sparse, "state": state, "linear": linear,
            "proj": proj, "plan": plan,
            "total": sparse + state + linear + proj + plan}
