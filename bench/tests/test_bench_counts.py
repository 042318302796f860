"""The benchmark's FLOP counts are copies of the program's
`core/flops.py` (so the yardstick stays fixed): they must agree at small
sizes. Kernel and model counts follow from shapes."""
import pytest

from bench.counts import kernels, models, sla
from bench.tests import small
from repro.core import flops as program_flops
from repro.core.config import SLAConfig


@pytest.mark.parametrize("n,d,h,kh,kl", [(256, 32, 4, 0.25, 0.25),
                                         (1024, 64, 2, 0.05, 0.1),
                                         (4096, 128, 3, 0.05, 0.1)])
def test_sla_flops_match_the_program(n, d, h, kh, kl):
    cfg = SLAConfig(block_q=64 if n >= 1024 else 16,
                    block_kv=64 if n >= 1024 else 16, kh_frac=kh,
                    kl_frac=kl)
    mine = sla.sla_flops(n, d, h, {"block_q": cfg.block_q,
                                   "block_kv": cfg.block_kv,
                                   "kh_frac": kh})
    theirs = program_flops.sla_flops(n, d, h, cfg)
    for key in ("sparse", "linear", "mask", "aggregate", "proj", "total"):
        assert mine[key] == pytest.approx(theirs[key], rel=1e-12)


@pytest.mark.parametrize("budget", [None, 3])
def test_sla_decode_flops_match_the_program(budget):
    cfg = SLAConfig(block_q=16, block_kv=16, kh_frac=0.125,
                    decode_budget=budget)
    mine = sla.sla_decode_flops(512, 32, 4, {"block_q": 16, "block_kv": 16,
                                             "kh_frac": 0.125,
                                             "decode_budget": budget})
    theirs = program_flops.sla_decode_flops(512, 32, 4, cfg)
    for key in ("sparse", "state", "linear", "proj", "plan", "total"):
        assert mine[key] == pytest.approx(theirs[key], rel=1e-12)


def test_model_counts_grow_with_width_and_length():
    m = small.DIT_CONFIG["model"]
    assert models.dit_slot_step(m, 512) > 2 * models.dit_slot_step(m, 256) \
        * 0.99
    lm = small.LM_CONFIG["model"]
    per_param = 2 * lm["num_layers"] * (
        lm["d_model"] * (lm["num_heads"] + 2 * lm["num_kv_heads"])
        * lm["head_dim"] + lm["num_heads"] * lm["head_dim"] * lm["d_model"]
        + 3 * lm["d_model"] * lm["d_ff"])
    assert models.lm_linear_per_token(lm) == per_param
    assert models.lm_decode_token(lm, 256) > per_param
    assert models.lm_prefill_token(lm, 128) > per_param


def test_kernel_counts():
    f, b = kernels.sla_fwd(1024, 128, 4, 2, 64, 3, 2)
    assert f == 4 * (4.0 * 1024 * 3 * 64 * 128 + 2.0 * 1024 * 128 * 128
                     + 2.0 * 1024 * 128)
    # K and V are read once per kv head, however many rows pick a block
    f1, b1 = kernels.sla_fwd(1024, 128, 4, 2, 64, 6, 2)
    assert f1 > f and b1 == b
    fd, bd = kernels.sla_decode(3, 128, 16, 8, 64, 2, 2)
    assert fd > 0 and bd > 0
    fd2, bd2 = kernels.sla_decode(6, 128, 16, 8, 64, 2, 2)
    assert fd2 == 2 * fd and bd2 == 2 * bd
