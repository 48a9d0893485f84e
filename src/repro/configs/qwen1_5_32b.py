"""qwen1.5-32b [dense] — QKV bias, GQA.

64L d_model=5120 40H (GQA kv=8, head_dim=128) d_ff=27392 vocab=152064
[hf:Qwen/Qwen1.5-32B, config.json].
40 q and 8 kv heads both divide a 2-way ``model`` axis: no head padding.
HBM note: with 8 kv heads the bf16 KV cache at decode_32k batch 128 is
4.3 GB/chip on the 16x16 production mesh (25.8 GB with 40 padded to 48),
so it stays in bf16.
Attention is ``chunked``: training at 4k-token rows, ``recursive``'s f32
score blocks kept for the backward pass do not fit a v5e beside a 2x2
host's share of the weights and AdamW state; each chunk is recomputed.
"""
from ..models.model import ModelConfig
from .base import register

CONFIG = register(ModelConfig(
    name="qwen1.5-32b",
    n_layers=64, d_model=5120, n_heads=40, n_kv_heads=8, head_dim=128,
    d_ff=27392, vocab=152064,
    qkv_bias=True, rope_theta=1e6, attn_impl="chunked",
))
