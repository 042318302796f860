"""Roofline analysis from compiled dry-run artifacts (TPU v5e model).

Three terms per (arch x shape x mesh):
  compute    = HLO_FLOPs / (chips * 197e12 bf16 FLOP/s)
  memory     = HLO_bytes / (chips * 819e9 B/s HBM)
  collective = collective_bytes / (chips * 50e9 B/s per ICI link)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis(); collective
bytes are parsed out of the HLO text (operand sizes of all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute), since
cost_analysis does not report them.
"""
from __future__ import annotations

import re
from typing import Dict

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s / chip
ICI_BW = 50e9  # B/s / link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes per collective kind from HLO text.

    Two passes: build a {name: result_shape} table, then for each
    collective op sum the byte sizes of its operand names.
    """
    shapes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)

    out = {k: 0.0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        op = m.group(3)
        kind = next((c for c in _COLLECTIVES if op.startswith(c)), None)
        if kind is None:
            continue
        # operand names inside the first (...) after the op name
        call = line[m.end():]
        paren = call.find("(")
        if paren < 0:
            continue
        depth, j = 0, paren
        for j in range(paren, len(call)):
            depth += call[j] == "("
            depth -= call[j] == ")"
            if depth == 0:
                break
        args = call[paren + 1: j]
        nbytes = 0
        for name in re.findall(r"%?([\w.\-]+)", args):
            if name in shapes:
                nbytes += _shape_bytes(shapes[name])
        if nbytes == 0:  # fallback: result shape
            nbytes = _shape_bytes(m.group(2))
        out[kind] += nbytes
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_terms(flops_total: float, bytes_total: float,
                   coll_bytes: float, chips: int) -> Dict[str, float]:
    """All three terms in seconds PER CHIP (inputs are whole-program)."""
    compute = flops_total / (chips * PEAK_FLOPS)
    memory = bytes_total / (chips * HBM_BW)
    collective = coll_bytes / (chips * ICI_BW)
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    total = max(compute, memory, collective)
    terms["bound_s"] = total
    terms["roofline_fraction"] = compute / total if total > 0 else 0.0
    return terms


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) per step.

    N counts *active* parameters touched per token; D is tokens
    processed. For decode shapes D = global_batch (one token each);
    training uses 6ND (fwd+bwd), inference 2ND."""
    params_active = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * params_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * params_active * tokens
    return 2.0 * params_active * shape.global_batch


def active_params(cfg) -> float:
    """Rough active-parameter count from the config (per token)."""
    d, l = cfg.d_model, cfg.num_layers
    attn = d * cfg.head_dim * (cfg.num_heads * 2
                               + cfg.num_kv_heads * 2) * l
    if cfg.num_experts:
        k = cfg.experts_per_token + (1 if cfg.moe_shared_expert else 0)
        ffn = 3.0 * d * cfg.moe_d_ff * k * l
    else:
        ffn = 3.0 * d * cfg.d_ff * l
    if cfg.family == "ssm":
        attn = 6.0 * d * d * l  # r,k,v,g,o + lora
        ffn = 2.5 * d * cfg.d_ff * l
    if cfg.family == "hybrid":
        h, pd, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        attn = (d * (2 * h * pd + 2 * n + h) + h * pd * d) * l
        nseg = max(1, cfg.num_layers // max(cfg.attn_every, 1))
        ffn = (d * cfg.head_dim * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)
               + 3 * d * cfg.d_ff) * nseg
    emb = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    if cfg.is_dit:
        emb = cfg.patch_dim * d * 2
    return attn + ffn + emb
