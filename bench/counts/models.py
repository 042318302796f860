"""Model FLOPs of one step, by what the mathematics needs: matrix
products, SLA attention at the configured critical fraction (sparse,
linear and Proj; planning left out), cross-attention, the output head.
Recomputation and padding are not counted."""
from __future__ import annotations

from bench.counts.sla import sla_decode_flops, sla_flops


def dit_slot_step(model: dict, seq_len: int) -> float:
    """FLOPs of one denoise step of one sample of `seq_len` tokens."""
    d, h, hkv, dh = (model["d_model"], model["num_heads"],
                     model["num_kv_heads"], model["head_dim"])
    ff, p, lc = model["d_ff"], model["patch_dim"], model["cond_len"]
    n = seq_len
    per_token = (2 * d * (h + 2 * hkv) * dh + 2 * h * dh * d   # qkv, wo
                 + 2 * d * h * dh + 2 * h * dh * d             # xq, xo
                 + 2 * d * 2 * ff + 2 * ff * d                 # gated MLP
                 + 4 * lc * dh * h)                            # cross-attn
    sla = sla_flops(n, dh, h, model["sla"])
    per_layer = (n * per_token + sla["sparse"] + sla["linear"] + sla["proj"]
                 + 2 * d * 6 * d                               # AdaLN
                 + 2 * lc * d * 2 * hkv * dh)                  # cross k, v
    return (model["num_layers"] * per_layer
            + n * 2 * p * d * 2                                # patch in/out
            + 2 * 256 * d)                                     # t_embed


def lm_linear_per_token(model: dict) -> float:
    d, h, hkv, dh, ff = (model["d_model"], model["num_heads"],
                         model["num_kv_heads"], model["head_dim"],
                         model["d_ff"])
    return model["num_layers"] * (2 * d * (h + 2 * hkv) * dh
                                  + 2 * h * dh * d + 2 * d * 2 * ff
                                  + 2 * ff * d)


def lm_prefill_token(model: dict, bucket: int) -> float:
    """FLOPs per prompt token prefilled in a `bucket`-token prefill."""
    sla = sla_flops(bucket, model["head_dim"], model["num_heads"],
                    dict(model["sla"], block_q=model["sla"]["block_q"]))
    attn = (sla["sparse"] + sla["linear"] + sla["proj"]) / bucket
    return lm_linear_per_token(model) + model["num_layers"] * attn


def lm_decode_token(model: dict, max_len: int) -> float:
    """FLOPs per decoded token (decode-time SLA on the max_len grid),
    with the output head."""
    dec = sla_decode_flops(max_len, model["head_dim"], model["num_heads"],
                           model["sla"])
    attn = dec["sparse"] + dec["state"] + dec["linear"] + dec["proj"]
    return (lm_linear_per_token(model) + model["num_layers"] * attn
            + lm_logits(model))


def lm_logits(model: dict) -> float:
    return 2.0 * model["d_model"] * model["vocab_size"]
