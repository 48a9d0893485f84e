"""Metric readers on made-up runs: what each reads, and that a reader
with nothing to read returns nothing."""
from types import SimpleNamespace as NS

import pytest

from bench.harness import core, work
from bench.harness.peaks import PEAKS
from bench.harness.record import Record
from bench.harness.xplane import Reduced

PEAK = PEAKS["TPU v5 lite"]
QWEN = core.load_json(f"{core.BENCH}/configs/qwen3-0.6b.json")
MM3 = core.load_json(f"{core.BENCH}/configs/polybench-3mm-xl.json")


def run_of(**kw):
    base = dict(trace=None, record=Record(), config={}, mix={},
                device={"used": [0]}, peaks=PEAK, counters={}, entry=None,
                setup_s=0.0)
    base.update(kw)
    return NS(**base)


def reduced(busy=0.5, window=1.0, ops=None, modules=None):
    return Reduced(window_s=window, busy_s=busy, ops=ops or {},
                   modules=modules or {}, gaps=[], chips=1)


def read(name, run):
    return core.metric_reader(name)(run)


def test_idle_share():
    assert read("idle_share.model", run_of(trace=reduced(0.25, 1.0))) \
        == pytest.approx(75.0)
    assert read("idle_share.plan", run_of(trace=None)) is None


def test_end_to_end_rates():
    rec = Record(window_s=2.0, new_tokens=100, optimized=30,
                 latencies=[0.001 * i for i in range(1, 101)])
    r = run_of(record=rec, setup_s=12.5)
    assert read("tokens_per_s", r) == 50.0
    assert read("plan_req_s", r) == 15.0
    assert read("plan_p95_ms", r) == pytest.approx(95.05)
    assert read("setup_s", r) == 12.5


def test_mfu_model_counts_completed_requests():
    mix = {"new_tokens": 4, "batch": 2}
    rec = Record(window_s=0.5, batches=[{"prompt": 8}, {"prompt": 16}])
    r = run_of(record=rec, config=QWEN, mix=mix)
    flops = 2 * (work.request_flops(QWEN, 8, 4)
                 + work.request_flops(QWEN, 16, 4))
    assert read("mfu.model", r) == pytest.approx(
        100 * flops / 0.5 / PEAK["bf16_flops"])


def test_hbm_share_decode_needs_every_step_in_the_trace():
    mix = {"new_tokens": 3, "batch": 2}
    rec = Record(batches=[{"prompt": 10}])
    nbytes = sum(work.decode_step_bytes(QWEN, [10 + k] * 2)
                 for k in (1, 2, 3))
    good = reduced(modules={"jit_decode_step": [3, 0.01],
                            "jit_prefill": [1, 0.5]})
    r = run_of(record=rec, config=QWEN, mix=mix, trace=good)
    assert read("hbm_share.decode", r) == pytest.approx(
        100 * nbytes / 0.01 / PEAK["hbm_bytes_per_s"])
    short = reduced(modules={"jit_decode_step": [2, 0.01]})
    assert read("hbm_share.decode", run_of(record=rec, config=QWEN, mix=mix,
                                           trace=short)) is None


def test_plan_metrics():
    rec = Record(window_s=1.0, completed=10, optimized=10)
    entry = NS(plan_latency_s=0.001)
    r = run_of(record=rec, config=MM3, trace=reduced(busy=0.04),
               entry=entry)
    assert read("cost_model_x", r) == pytest.approx(4.0)
    assert read("mfu.plan", r) == pytest.approx(
        100 * work.mm3_flops(MM3) * 10 / PEAK["bf16_flops"])
