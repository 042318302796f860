"""Small cells for the CPU tests: the same runners, references and data
layout as the chip cells, at widths a test run can hold."""
from __future__ import annotations

import copy

from bench import spec

DIT_CONFIG = {
    "arch": "wan2_1_1_3b", "family": "dit", "reference": "dit",
    "weight_dtype": "bfloat16",
    "compute_dtype": "float32", "product_inputs": "bfloat16",
    "backend": "gather",
    "model": {"num_layers": 2, "d_model": 128, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 32, "d_ff": 256,
              "vocab_size": 0, "patch_dim": 16, "cross_attn": True,
              "cond_len": 16,
              "sla": {"block_q": 16, "block_kv": 16, "kh_frac": 0.25,
                      "kl_frac": 0.25, "phi": "softmax",
                      "col_capacity_factor": 2.0,
                      "plan_refresh_mode": "fixed",
                      "plan_refresh_interval": 1}}}
DIT_TRAFFIC = {"kind": "dit", "loop": "backlog", "seq_len": 256,
               "slots": 2, "num_steps": 6, "t_start": 1.0,
               "fill_steps": [6, 3], "backlog": 4, "cond_pool": 2}
LM_CONFIG = {
    "arch": "qwen3-1.7b", "family": "lm", "reference": "lm",
    "weight_dtype": "float32",
    "compute_dtype": "float32", "backend": "gather",
    "serving": {"slots": 3, "max_len": 256, "prefill_bucket": 128},
    "model": {"num_layers": 2, "d_model": 128, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 32, "d_ff": 256,
              "vocab_size": 512, "qk_norm": True, "rope_theta": 1e6,
              "tie_embeddings": True,
              "sla": {"block_q": 16, "block_kv": 16, "kh_frac": 0.125,
                      "kl_frac": 0.1, "phi": "softmax",
                      "col_capacity_factor": 2.0,
                      # always classify the live row afresh: the
                      # program's inherited-row path departs from the
                      # reference (PERF.md, Open questions)
                      "plan_drift_threshold": 0.0, "decode_budget": None}}}
LM_TRAFFIC = {
    "kind": "lm", "loop": "backlog", "shape_seed": 7, "pool": 12,
    "order_block": 4,
    "mix": [{"name": "a", "share": 0.5,
             "prompt": {"median": 60, "sigma": 0.5, "min": 8, "max": 128},
             "output": {"median": 40, "sigma": 0.5, "min": 4, "max": 128}},
            {"name": "b", "share": 0.5,
             "prompt": {"median": 100, "sigma": 0.5, "min": 8, "max": 128},
             "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 32}}],
    "warmup": {"requests": 2, "prompt": 16, "output": 3}}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def dit_cell(limit=1e-4, **config_changes):
    cfg = copy.deepcopy(DIT_CONFIG)
    cfg.update(config_changes)
    return spec.Cell(
        "small-dit", 1, cfg, copy.deepcopy(DIT_TRAFFIC),
        {"trace_seconds": 2,
         "limits": {"dit_step_excess_err": limit}},
        [{"name": "dit_tokens_per_s", "unit": "tokens/s"},
         {"name": "setup_s", "unit": "s"}], [])


def lm_cell(limit=1e-3, loop="backlog", **config_changes):
    cfg = copy.deepcopy(LM_CONFIG)
    cfg.update(config_changes)
    tr = copy.deepcopy(LM_TRAFFIC)
    e2e = [{"name": "lm_tokens_per_s", "unit": "tokens/s"}]
    if loop == "open":
        tr.update(loop="open", rate_per_s=4.0, pool=64)
        e2e = [{"name": "ttft_p90_ms", "unit": "ms"},
               {"name": "itl_p95_ms", "unit": "ms"}]
    return spec.Cell(
        "small-lm", 1, cfg, tr,
        {"check_requests": 3, "trace_seconds": 2,
         "limits": {"lm_logit_gap": limit}},
        e2e + [{"name": "setup_s", "unit": "s"}], [])
