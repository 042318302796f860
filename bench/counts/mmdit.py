"""Model FLOPs of one denoise step of the dual-stream then single-stream
MMDiT (models/mmdit.py), by what the mathematics needs: every matrix
product, SLA over the joint sequence of video and text tokens at the
configured critical fraction (sparse, linear and Proj; planning left
out), the modulation products and the embedders. Recomputation and
padding are not counted."""
from __future__ import annotations

from bench.counts.sla import sla_flops


def block_per_token(model: dict) -> float:
    """FLOPs per token of one block's token-wise products: q, k, v, the
    attention output and the MLP (in either kind of block)."""
    d, hdh, ff = (model["d_model"], model["num_heads"] * model["head_dim"],
                  model["d_ff"])
    return 2 * d * 3 * hdh + 2 * hdh * d + 2 * d * ff + 2 * ff * d


def slot_step(model: dict, seq_len: int) -> float:
    """FLOPs of one denoise step of one sample of `seq_len` video tokens
    (and the configuration's `cond_len` text tokens)."""
    d, p, lc = model["d_model"], model["patch_dim"], model["cond_len"]
    nd = model["double_layers"]
    ns = model["num_layers"] - nd
    joint = seq_len + lc
    sla = sla_flops(joint, model["head_dim"], model["num_heads"],
                    model["sla"])
    attn = sla["sparse"] + sla["linear"] + sla["proj"]
    per_layer = joint * block_per_token(model) + attn
    mods = nd * 2 * (2 * d * 6 * d) + ns * 2 * d * 3 * d + 2 * d * 2 * d
    embed = (2 * seq_len * p * d * 2                # patch in and out
             + 2 * 256 * d + 2 * d * d)             # time_in
    return (nd + ns) * per_layer + mods + embed
