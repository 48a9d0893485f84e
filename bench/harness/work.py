"""The operations and bytes the algorithms need, from their shapes.

Everything here counts what the computation requires, not what the
program happens to do: attention over the positions a token can see
(not over a padded cache), logits only where a token is chosen, each
contraction's operands at their unpadded shapes.  Configurations are the
published key names of ``bench/configs``.
"""
from __future__ import annotations


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["num_hidden_layers"], cfg["vocab_size"])


def layer_weights(cfg: dict) -> int:
    """Parameters of the linear layers of one decoder layer."""
    d, f, hq, hkv, hd, _, _ = _dims(cfg)
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def token_flops(cfg: dict, context: int) -> float:
    """One token through every decoder layer, attending to ``context``
    positions (its own included); no logits."""
    _, _, hq, _, hd, n, _ = _dims(cfg)
    return n * (2.0 * layer_weights(cfg) + 4.0 * hq * hd * context)


def logits_flops(cfg: dict) -> float:
    d, *_, v = _dims(cfg)
    return 2.0 * d * v


def request_flops(cfg: dict, prompt: int, new_tokens: int) -> float:
    """A prompt of ``prompt`` tokens and ``new_tokens`` greedy tokens: the
    prompt's tokens with causal contexts 1..prompt and logits at its last
    position, then ``new_tokens - 1`` decoded tokens, each with logits."""
    total = sum(token_flops(cfg, c) for c in range(1, prompt + 1))
    total += logits_flops(cfg)
    for k in range(1, new_tokens):
        total += token_flops(cfg, prompt + k) + logits_flops(cfg)
    return total


def kv_bytes_per_position(cfg: dict, dtype_bytes: int = 2) -> int:
    """Key and value bytes of one position over every layer."""
    _, _, _, hkv, hd, n, _ = _dims(cfg)
    return 2 * n * hkv * hd * dtype_bytes


def decode_step_bytes(cfg: dict, contexts, dtype_bytes: int = 2) -> float:
    """HBM bytes one decode step needs for a batch whose rows see
    ``contexts`` positions each: every weight once (layers, final norm,
    logits matrix, the embedding rows looked up), the keys and values at
    the positions each row attends to, and the new position written."""
    d, _, _, _, hd, n, v = _dims(cfg)
    rows = len(contexts)
    weights = n * (layer_weights(cfg) + 2 * d + 2 * hd) + d + d * v
    weights += rows * d                              # embedding rows
    kv = kv_bytes_per_position(cfg, dtype_bytes)
    return dtype_bytes * weights + kv * (sum(contexts) + rows)


def mm3_contractions(cfg: dict, dtype_bytes: int = 4) -> list[dict]:
    """PolyBench 3mm's three products at their unpadded shapes:
    E = A.B, F = C.D, G = E.F; each reads its operands and writes its
    result once."""
    ni, nj, nk, nl, nm = (cfg[k] for k in ("NI", "NJ", "NK", "NL", "NM"))
    out = []
    for name, (m, k, n) in (("E", (ni, nk, nj)), ("F", (nj, nm, nl)),
                            ("G", (ni, nj, nl))):
        out.append({"name": name, "flops": 2.0 * m * k * n,
                    "bytes": float(dtype_bytes * (m * k + k * n + m * n))})
    return out


def mm3_flops(cfg: dict) -> float:
    return sum(c["flops"] for c in mm3_contractions(cfg))


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    compute = flops / peak["bf16_flops"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
