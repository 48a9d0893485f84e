"""The operations a training step needs, from the configuration's
published keys (``bench/configs``, Qwen2 names; ``head_dim`` is
``hidden_size / num_attention_heads``).

Counted as the model requires them, not as the program runs them: every
matrix product of the layers and the output head three times over (the
forward pass, and the backward pass's two products), none recomputed, and
causal attention over the positions each token sees.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights of the matrix products a token goes through: the layers'
    projections and the output head (not the embedding, a lookup)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    layer = 2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def token_flops(cfg: dict, seq_len: int) -> float:
    """One token of a packed row of ``seq_len`` through a training step:
    6 FLOPs per matmul weight, and per layer 12 * heads * head_dim FLOPs
    per position seen (scores and values, forward and backward), at the
    row's mean causal context ``(seq_len + 1) / 2``."""
    d = cfg["hidden_size"]
    attn = 12.0 * d * (seq_len + 1) / 2 * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attn
