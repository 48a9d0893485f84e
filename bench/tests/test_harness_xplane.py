"""The trace reduction on made-up planes laid out as a TPU trace is:
busy union, clipping to the window, per-program and per-operation time,
idle gaps named by the harness's spans."""
from types import SimpleNamespace as NS

import pytest

from bench.harness import xplane


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_union_merges_overlaps():
    assert xplane.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3),
                                                              (5, 10)]


def test_module_name_drops_the_id():
    assert xplane.module_name("jit_decode_step(123)") == "jit_decode_step"


def test_reduce_made_up_planes():
    host = plane("/host:CPU", python=[
        ev("bench.window", 100, 1100), ev("bench.request", 100, 600),
        ev("bench.wait", 600, 1100), ev("other", 0, 2000)])
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev("jit_f(7)", 50, 400),
                             ev("jit_g(8)", 700, 900)],
                XLA_Ops=[ev("fusion.1", 50, 200), ev("fusion.2", 150, 400),
                         ev("copy.3", 700, 800), ev("copy.3", 850, 900)])
    r = xplane.reduce([host, dev], chips=1)
    assert r.window_s == pytest.approx(1e-6)
    # ops clipped to the window [100, 1100): 100-400, 700-800, 850-900
    assert r.busy_s == pytest.approx(450e-9)
    assert r.modules == {"jit_f": [1, pytest.approx(300e-9)],
                         "jit_g": [1, pytest.approx(200e-9)]}
    assert r.op_seconds(lambda m, o: o == "copy.3") == (2, pytest.approx(
        150e-9))
    assert r.ops[("jit_f", "fusion.2")] == [1, pytest.approx(250e-9)]
    # holes: 400-700 (in bench.request until 600; middle 550), 800-850
    # (bench.wait), 900-1100 (bench.wait)
    assert r.gaps == [("bench.request", pytest.approx(300e-9)),
                      ("bench.wait", pytest.approx(200e-9)),
                      ("bench.wait", pytest.approx(50e-9))]
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit_f/fusion.2", pytest.approx(250e-9)]
    assert len(b["idle_gaps"]) == 3


def test_no_window_span_is_an_error():
    dev = plane("/device:TPU:0", XLA_Ops=[ev("x", 0, 1)])
    with pytest.raises(ValueError, match="bench.window"):
        xplane.reduce([dev], chips=1)


RECORDED = __import__("os").path.join(__import__("os").path.dirname(
    __file__), "data", "mm3-xl-closed.xplane.pb")


def test_reduce_recorded_tpu_trace():
    """A 50 ms traced window of the 3mm cell on a TPU v5e: 15 requests,
    each one execution of ``jit_body`` with three contraction kernels
    (``body.3``-``body.5``, named in the trace by their whole HLO line).
    Counts and times checked by hand against the raw events: 45 kernel
    events, 0.0395 s of them before clipping to the window."""
    import re

    r = xplane.reduce_file(RECORDED, chips=1)
    assert r.window_s == pytest.approx(0.053712239)
    assert r.busy_s == pytest.approx(0.040220643)
    assert r.modules == {"jit_body": [15, pytest.approx(0.04022143)]}
    kernel = re.compile(r"^(body|kernel)(\.\d+)?$")
    n, secs = r.op_seconds(lambda m, o: kernel.match(o) is not None)
    assert n == 45 and secs == pytest.approx(0.0386205, rel=1e-4)
    assert {o for (_, o), (c, _) in r.ops.items() if kernel.match(o)} \
        == {"body.3", "body.4", "body.5"}
    assert r.gaps[0][0] == "bench.request"


def test_op_name_keeps_the_instruction():
    assert xplane.op_name("%body.5 = f32[16,8]{1,0} custom-call(%a)") \
        == "body.5"
    assert xplane.op_name("fusion.2") == "fusion.2"
