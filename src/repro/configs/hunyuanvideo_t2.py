"""hunyuanvideo-t2 — HunyuanVideo's HYVideo-T/2 video MMDiT: 20 dual-stream
then 40 single-stream blocks, joint attention over the video tokens and
256 text tokens, q/k RMSNorm, 3-D RoPE split 16/56/56 at theta 256.
[github.com/Tencent/HunyuanVideo, hyvideo/modules/models.py]"""
from repro.configs.base import ArchConfig
from repro.core.config import SLAConfig

CONFIG = ArchConfig(
    name="hunyuanvideo_t2", family="mmdit",
    num_layers=60, double_layers=20, d_model=3072, num_heads=24,
    num_kv_heads=24, head_dim=128, d_ff=12288, vocab_size=0,
    qk_norm=True, rope_theta=256.0, rope_axes=(16, 56, 56),
    latent_hw=(34, 60),  # 544 x 960 pixels, VAE x8, patch 2
    patch_dim=64, cond_len=256,
    sla=SLAConfig(kh_frac=0.05, kl_frac=0.10, phi="softmax",
                  block_q=64, block_kv=64),
)
