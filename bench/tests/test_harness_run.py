"""Whole runs of tiny cells on the CPU, with the chip check skipped: the
result line, the check and the control."""
import pytest

KINDS = ["model", "mm3", "ffn"]


@pytest.mark.parametrize("kind", KINDS)
def test_tiny_cell_is_correct(tiny, kind):
    r = tiny.run_cell(kind, seed=1).result
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checked"
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    for ch in r["checked"].values():
        assert 0.0 <= ch["value"] <= ch["limit"]


def test_large_seed_runs(tiny):
    assert tiny.run_cell("mm3", seed=2**33 + 5).result["correct"] is True


@pytest.mark.parametrize("kind", KINDS)
def test_control_fails_the_limit(tiny, kind):
    """The reference one precision below the configuration's, in the
    program's place, comes out as not correct by the run's own rule."""
    from bench.harness import core
    run = tiny.run_cell(kind, seed=1)
    assert core.judge(run.entry.control(run.record)) is False
