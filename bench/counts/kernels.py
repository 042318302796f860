"""FLOPs and the least bytes one call of each SLA kernel needs, from
its shapes. Bytes count every operand read once and every result
written once (K and V once per head, not once per query row that picks
a block), so a kernel that reuses tiles better can only come closer to
its roofline, never pass it."""
from __future__ import annotations


def sla_fwd(n: int, d: int, heads: int, kv_heads: int, block: int,
            n_crit: int, in_bytes: int) -> tuple:
    """One forward launch set over `heads` query heads of one sample:
    sparse softmax over n_crit critical blocks per query block, merged
    with the linear branch phi(Q)H_i / phi(Q)Z_i. Inputs q, k, v, phi(q)
    of `in_bytes` each; H_i, Z_i and the outputs (o_s, o_l, lse) f32."""
    tm = n // block
    flops = heads * (4.0 * n * n_crit * block * d + 2.0 * n * d * d
                     + 2.0 * n * d)
    read = (heads * 2 * n * d * in_bytes            # q, phi(q)
            + kv_heads * 2 * n * d * in_bytes       # k, v
            + heads * tm * (d * d + d) * 4)         # H_i, Z_i
    write = heads * (2 * n * d + n) * 4
    return flops, float(read + write)


def sla_decode(slots: int, d: int, heads: int, kv_heads: int, block: int,
               k_sel: int, kv_bytes: int) -> tuple:
    """One decode launch (one token per slot, one layer): per query head
    attention over k_sel critical blocks and the subtractive linear
    branch against the H/Z totals. Least bytes: each kv head's k_sel
    K/V blocks and their h/z partials once, the per-kv-head totals, the
    query rows, the outputs."""
    flops = slots * heads * (4.0 * k_sel * block * d + 2.0 * k_sel * d * d
                             + 2.0 * d * d + 2.0 * d)
    per_kv = (k_sel * (2 * block * d * kv_bytes + (d * d + d) * 4)
              + (d * d + d) * 4)
    per_q = 2 * d * 4 + 2 * d * 4                   # q, phi(q), o_s, o_l
    return flops, float(slots * (kv_heads * per_kv + heads * per_q))
