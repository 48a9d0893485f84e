"""Work counts and the table of peaks, against values worked by hand."""
import pytest

from bench.harness import peaks, work

TINY = {"hidden_size": 4, "intermediate_size": 6, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "num_hidden_layers": 3,
        "vocab_size": 10}


def test_layer_weights_by_hand():
    # q 4x4 + k,v 4x2 each + o 4x4 + gate, up 4x6 + down 6x4
    assert work.layer_weights(TINY) == 16 + 8 + 8 + 16 + 72


def test_token_flops_by_hand():
    # per layer: 2 x 120 weights + attention 4 x heads 2 x dim 2 x context 5
    assert work.token_flops(TINY, 5) == 3 * (240 + 80)


def test_request_flops_by_hand():
    # prompt of 2 (contexts 1, 2), logits once, then 2 decoded tokens
    # (contexts 3, 4) with logits each; logits = 2 x 4 x 10 = 80
    per = [3 * (240 + 16 * c) for c in (1, 2, 3, 4)]
    assert work.request_flops(TINY, 2, 3) == sum(per) + 3 * 80


def test_decode_step_bytes_by_hand():
    layer = 120 + 2 * 4 + 2 * 2          # linear weights, two norms, q/k norm
    weights = 3 * layer + 4 + 4 * 10 + 2 * 4   # final norm, logits, 2 rows
    kv = 2 * 3 * 1 * 2 * 2               # k and v, 3 layers, 1 head, dim 2
    assert work.decode_step_bytes(TINY, [5, 7]) == 2 * weights + kv * 14


def test_mm3_by_hand():
    cfg = {"NI": 2, "NJ": 3, "NK": 4, "NL": 5, "NM": 6}
    got = {c["name"]: (c["flops"], c["bytes"])
           for c in work.mm3_contractions(cfg)}
    assert got == {"E": (2 * 2 * 4 * 3, 4 * (8 + 12 + 6)),
                   "F": (2 * 3 * 6 * 5, 4 * (18 + 30 + 15)),
                   "G": (2 * 2 * 3 * 5, 4 * (6 + 15 + 10))}
    assert work.mm3_flops(cfg) == 2 * (24 + 90 + 30)


def test_roofline_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.roofline_seconds(197e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.roofline_seconds(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v4")
