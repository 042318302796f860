"""The Pallas kernels compile for a TPU v5e at the benchmark widths.

Interpret mode (every other kernel test) cannot see Mosaic's tiling
rules, its SMEM budget or its VMEM limit. Here each kernel is lowered
with `interpret=False` and compiled by the TPU compiler for one chip of
a described `v5e:2x2` topology — no chip is attached, nothing runs — and
the program must hold the kernel as a `tpu_custom_call`.

The topology is described inside a module fixture only: describing it
loads the TPU library, which one process at a time may hold.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sla_bwd import sla_bwd_dkv, sla_bwd_dq
from repro.kernels.sla_decode import _fused_decode, _fused_decode_paged
from repro.kernels.sla_fwd import sla_fwd

I32, F32, BF16 = jnp.int32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


# (B*H, B*H_kv, N, D, causal, K_sel): Wan2.1 video (12 x 128, 32k tokens,
# bidirectional), Qwen3-1.7B prefill (GQA 16/8 x 128, causal, 32k),
# LightningDiT (16 x 108 — not lane-aligned — at 1024 tokens) and
# HunyuanVideo's joint attention (one group of 8 of its 24 heads at
# 65,536 tokens: 2 heads per launch fill SMEM with 104,448 LUT entries)
FWD = {
    "wan2_1_1_3b": (12, 12, 32768, 128, False, 26),
    "hunyuanvideo-t2": (8, 8, 65536, 128, False, 51),
    "qwen3-1.7b": (16, 8, 32768, 128, True, 26),
    "lightningdit_1b": (16, 16, 1024, 108, False, 2),
}


@pytest.mark.parametrize("arch", list(FWD))
def test_sla_fwd_compiles(one_chip, arch):
    bh, bh_kv, n, d, causal, k_sel = FWD[arch]
    tm = n // 64
    fn = functools.partial(sla_fwd, scale=d ** -0.5, causal=causal,
                           block_q=64, block_kv=64, interpret=False)
    _compile(fn, one_chip,
             ((bh, tm, k_sel), I32), ((bh, tm), I32),
             ((bh, n, d), BF16), ((bh_kv, n, d), BF16),
             ((bh_kv, n, d), BF16), ((bh, n, d), BF16),
             ((bh, tm, d, d), F32), ((bh, tm, d), F32))


@pytest.mark.parametrize("kernel", ["dq", "dkv"])
def test_sla_bwd_compiles(one_chip, kernel):
    """Both backward kernels at Wan2.1 width (12 x 128, 32k tokens)."""
    bh, n, d, width = 12, 32768, 128, 52
    fn = functools.partial(sla_bwd_dq if kernel == "dq" else sla_bwd_dkv,
                           scale=d ** -0.5, causal=False, block_q=64,
                           block_kv=64, interpret=False)
    _compile(fn, one_chip,
             ((bh, n // 64, width), I32), ((bh, n // 64), I32),
             ((bh, n, d), BF16), ((bh, n, d), BF16), ((bh, n, d), BF16),
             ((bh, n, d), F32), ((bh, n), F32), ((bh, n), F32))


# Qwen3-1.7B decode: 4 slots, GQA 16/8 x 128, a 32k cache in 64-token
# blocks (Tn = 512), 26 critical blocks per token
B, H, HKV, D, BKV, TN, K = 4, 16, 8, 128, 64, 512, 26


def _decode_token_shapes(c):
    bh, bkh = B * H, B * HKV
    return [((bh, c, D), F32), ((bh, c, D), F32)], [
        ((bkh, c, D, D), F32), ((bkh, c, D), F32),
        ((bkh, c, D, D), F32), ((bkh, c, D), F32)]


def test_fused_decode_compiles(one_chip):
    """Flat-layout decode over a chunk of 4 tokens."""
    c = 4
    bh, bkh = B * H, B * HKV
    toks, tots = _decode_token_shapes(c)
    fn = functools.partial(_fused_decode, scale=D ** -0.5, block_kv=BKV,
                           group=H // HKV, interpret=False)
    _compile(fn, one_chip,
             ((bh, c, K), I32), ((bh, c), I32), ((bh, c), I32), ((bh,), I32),
             *toks,
             ((bkh, TN, BKV, D), BF16), ((bkh, TN, BKV, D), BF16),
             ((bkh, TN, D, D), F32), ((bkh, TN, D), F32), *tots)


def test_fused_decode_paged_compiles(one_chip):
    """Paged decode (single token) against a global page pool."""
    bh = B * H
    pages = 1 + B + B * TN
    toks, tots = _decode_token_shapes(1)
    fn = functools.partial(_fused_decode_paged, scale=D ** -0.5,
                           block_kv=BKV, group=H // HKV, hkv=HKV,
                           interpret=False)
    _compile(fn, one_chip,
             ((bh, 1, K), I32), ((bh, 1, K), I32), ((bh, 1), I32),
             ((bh, 1), I32), ((bh,), I32), *toks,
             ((HKV, pages, BKV, D), BF16), ((HKV, pages, BKV, D), BF16),
             ((HKV, pages, D, D), F32), ((HKV, pages, D), F32), *tots)


# (kernel name, jitted entry, static arguments, operand shapes) at small
# widths: one launch of each Pallas kernel
_S, _N, _T = 2, 512, 8
_ROWS = [((_S, _T, 2), I32), ((_S, _T), I32), ((_S, _N, D), BF16),
         ((_S, _N, D), BF16), ((_S, _N, D), BF16)]
_DEC = [((2, 1, 2), I32), ((2, 1), I32), ((2, 1), I32), ((2,), I32),
        ((2, 1, D), F32), ((2, 1, D), F32)]
_DEC_STATE = [((1, 1, D, D), F32), ((1, 1, D), F32), ((1, 1, D, D), F32),
              ((1, 1, D), F32)]
NAMED = {
    "sla_fwd": (sla_fwd, dict(causal=False, block_q=64),
                _ROWS + [((_S, _N, D), BF16), ((_S, _T, D, D), F32),
                         ((_S, _T, D), F32)]),
    "sla_bwd_dq": (sla_bwd_dq, dict(causal=False, block_q=64),
                   _ROWS + [((_S, _N, D), F32), ((_S, _N), F32),
                            ((_S, _N), F32)]),
    "sla_bwd_dkv": (sla_bwd_dkv, dict(causal=False, block_q=64),
                    _ROWS + [((_S, _N, D), F32), ((_S, _N), F32),
                             ((_S, _N), F32)]),
    "sla_decode": (_fused_decode, dict(group=2),
                   _DEC + [((1, _T, 64, D), BF16), ((1, _T, 64, D), BF16),
                           ((1, _T, D, D), F32), ((1, _T, D), F32)]
                   + _DEC_STATE),
    "sla_decode_paged": (_fused_decode_paged, dict(group=2, hkv=1),
                         _DEC[:1] + _DEC + [((1, 9, 64, D), BF16),
                                            ((1, 9, 64, D), BF16),
                                            ((1, 9, D, D), F32),
                                            ((1, 9, D), F32)]
                         + _DEC_STATE),
}


@pytest.mark.parametrize("name", list(NAMED))
def test_kernel_carries_its_name(one_chip, name):
    """Each kernel's custom call is named after the kernel, and its
    op_name ends with the kernel's name, whatever jitted function wraps
    it: the profile's op names and named scopes find it by that name."""
    entry, static, shapes = NAMED[name]
    fn = functools.partial(entry, scale=D ** -0.5, block_kv=64,
                           interpret=False, **static)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        head = ln.strip().removeprefix("ROOT ").split(" = ", 1)[0]
        assert head.startswith(f"%{name}."), head
        assert f'/{name}/pallas_call"' in ln
