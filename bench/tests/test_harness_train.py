"""The training cell's pieces on the CPU: a tiny cell's whole run against
the plain reference (on a 1x1 mesh, and on a 2x2 mesh of fake devices),
the control failing it, the traffic's batches, and the readers of the
three training metrics on made-up records and planes."""
import importlib
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import core, train_work
from bench.harness.peaks import PEAKS
from bench.harness.record import Record
from bench.harness.xplane import Reduced
from bench.tests import train_tiny
from bench.traffic import train_steps

PEAK = PEAKS["TPU v5 lite"]
QWEN2 = core.load_json(f"{core.BENCH}/configs/qwen1.5-32b.json")


@pytest.fixture
def tiny_train(monkeypatch, bench_state):
    monkeypatch.setattr(core, "STATE", bench_state)
    train_tiny.use_program_config(monkeypatch)
    return train_tiny


def test_tiny_train_cell_is_correct_and_its_control_is_not(tiny_train):
    run = tiny_train.run_cell(seed=2**33 + 7)
    r = run.result
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == run.record.completed > 0
    assert set(r["checked"]) == {"grad_err", "update_err", "decay_err"}
    for ch in r["checked"].values():
        assert 0.0 <= ch["value"] <= ch["limit"]
    assert core.judge(run.entry.control(run.record)) is False
    assert run.counters["repro_train_tokens_total"] == run.record.new_tokens


def test_tiny_train_reference_grad_norm(tiny_train):
    """The reference's whole gradient has the norm the program's first
    step reports, and the norm the reference itself reports."""
    from bench.refs import qwen2 as ref
    run = tiny_train.run_cell(seed=3)
    cfg, mix = run.config, run.mix
    every = {name: np.stack(np.unravel_index(np.arange(int(np.prod(s))),
                                             s), axis=1)
             for name, s in ref.shapes(cfg).items()}
    tokens, labels = train_steps.batch(3, 0, mix["global_batch"],
                                       mix["seq_len"], cfg["vocab_size"])
    loss, grads, ref_norm = ref.loss_and_grads(cfg, 3, tokens, labels,
                                               every)
    norm = np.sqrt(sum(float(np.sum(g * g)) for g in grads.values()))
    assert ref_norm == pytest.approx(norm, rel=1e-5)
    assert run.entry.first["grad_norm"] == pytest.approx(norm, rel=1e-2)
    assert run.entry.first["loss"] == pytest.approx(loss, rel=1e-3)


def test_tiny_train_cell_on_a_2x2_mesh():
    """The same cell on a 2x2 (data, model) mesh of four fake CPU devices:
    weights made sharded, the moment's sample gathered shard by shard."""
    code = textwrap.dedent("""
        import json, sys
        sys.path[:0] = [ROOT, ROOT + "/src"]
        import repro.configs
        from bench.tests import train_tiny as t
        real = repro.configs.get_config
        repro.configs.get_config = lambda n: t.program_config() \\
            if n == t.CONFIG["program_config"] else real(n)
        from bench.harness import core
        run = t.run_cell(seed=5, mesh=(2, 2))
        ctl = core.judge(run.entry.control(run.record))
        print(json.dumps({"correct": run.result["correct"],
                          "control": ctl}))
    """).replace("ROOT", repr(core.ROOT))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(core.STATE))
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == \
        '{"correct": true, "control": false}'


def _params_unchanged(monkeypatch):
    T = importlib.import_module("repro.train.train_step")
    real = T.adamw_update

    def kept(cfg, params, grads, state, **kw):
        _, new_state, metrics = real(cfg, params, grads, state, **kw)
        return params, new_state, metrics

    monkeypatch.setattr(T, "adamw_update", kept)


def _norm_doubled(monkeypatch):
    from repro.train import optimizer as O
    real = O.global_norm
    monkeypatch.setattr(O, "global_norm", lambda tree: 2 * real(tree))


def _second_moment_lost(monkeypatch):
    from repro.train import optimizer as O
    real = O.adamw_update

    def lost(*args, **kw):
        params, state, metrics = real(*args, **kw)
        v = jax.tree.map(jnp.zeros_like, state.v)
        return params, state._replace(v=v), metrics

    T = importlib.import_module("repro.train.train_step")
    monkeypatch.setattr(T, "adamw_update", lost)


def _decay_skipped(monkeypatch):
    from repro.models import model as M
    monkeypatch.setattr(M, "decay_mask",
                        lambda params: jax.tree.map(lambda x: False, params))


def _biases_decayed(monkeypatch):
    """Decay by the rank of the stacked tensor: the layers' norm gains and
    biases, stacked over layers, are decayed too."""
    from repro.models import model as M
    monkeypatch.setattr(M, "decay_mask", lambda params: jax.tree.map(
        lambda x: x.ndim >= 2, params))


@pytest.mark.parametrize("fault", [_params_unchanged, _norm_doubled,
                                   _second_moment_lost, _decay_skipped,
                                   _biases_decayed],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_train_step_turns_correct_false(tiny_train, monkeypatch,
                                              fault):
    """A first step whose optimizer leaves the parameters as they were,
    clips by a wrong norm, loses Adam's second moment, skips the weight
    decay or decays the biases is not correct."""
    fault(monkeypatch)
    r = tiny_train.run_cell(seed=7).result
    assert r["correct"] is False
    assert core.judge(r["checked"]) is False


def test_fault_local_gradients_on_a_2x2_mesh():
    """On a 2x2 mesh of fake devices, gradients each data shard takes from
    its own rows, never summed over ``data``, are not correct."""
    code = textwrap.dedent("""
        import json, sys
        sys.path[:0] = [ROOT, ROOT + "/src"]
        import importlib
        import jax
        from jax.sharding import PartitionSpec as P
        import repro.configs
        T = importlib.import_module("repro.train.train_step")
        from bench.tests import train_tiny as t
        real = repro.configs.get_config
        repro.configs.get_config = lambda n: t.program_config() \\
            if n == t.CONFIG["program_config"] else real(n)

        def local(params, opt_state, tokens, labels, *, cfg, opt_cfg,
                  microbatches=1):
            def grads(p, tk, lb):
                return jax.value_and_grad(T.loss_fn)(p, cfg, tk, lb)
            loss, g = jax.shard_map(
                grads, mesh=jax.sharding.get_abstract_mesh(),
                in_specs=(P(), P("data"), P("data")), out_specs=P(),
                check_vma=False)(params, tokens, labels)
            p, s, m = T.adamw_update(opt_cfg, params, g, opt_state)
            return p, s, {**m, "loss": loss}

        T.train_step = local
        from bench.harness import core
        run = t.run_cell(seed=5, mesh=(2, 2))
        print(json.dumps({"correct": run.result["correct"],
                          "judged": core.judge(run.result["checked"])}))
    """).replace("ROOT", repr(core.ROOT))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(core.STATE))
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == \
        '{"correct": false, "judged": false}'


def test_train_batches_repeat_for_a_seed():
    a = train_steps.batch(2**31 + 7, 3, 4, 16, 100)
    b = train_steps.batch(2**31 + 7, 3, 4, 16, 100)
    c = train_steps.batch(2**31 + 8, 3, 4, 16, 100)
    d = train_steps.batch(2**31 + 7, 4, 4, 16, 100)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    tokens, labels = a
    assert tokens.shape == labels.shape == (4, 16)
    assert tokens.dtype == np.int32
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])
    assert not np.array_equal(tokens, c[0])
    assert not np.array_equal(tokens, d[0])
    assert tokens.min() >= 0 and labels.max() < 100


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------
def run_of(**kw):
    base = dict(trace=None, record=Record(), config=QWEN2,
                mix={"seq_len": 4096}, device={"used": [0, 1, 2, 3]},
                peaks=PEAK, counters={}, entry=None, setup_s=0.0)
    base.update(kw)
    return NS(**base)


def read(name, run):
    return core.metric_reader(name)(run)


def test_train_flops_per_token():
    # 13.28 GFLOP a token at the cell's cut and 4096-token rows
    assert train_work.token_flops(QWEN2, 4096) == pytest.approx(
        1.3279e10, rel=1e-4)
    assert train_work.matmul_params(QWEN2) == 4 * 483_655_680 \
        + 5120 * 38016


def test_mfu_train_reads_the_program_tokens():
    rec = Record(window_s=10.0, new_tokens=16384 * 9)
    r = run_of(record=rec, counters={"repro_train_tokens_total": 16384 * 9})
    flops = 16384 * 9 * train_work.token_flops(QWEN2, 4096)
    assert read("mfu.train", r) == pytest.approx(
        100 * flops / 10.0 / (4 * PEAK["bf16_flops"]))
    r.counters["repro_train_tokens_total"] = 16384 * 8
    assert read("mfu.train", r) is None
    assert read("mfu.train", run_of(record=rec)) is None


def test_collective_share_counts_named_and_fused_collectives():
    ops = {("jit_step", "all-gather.3"): [4, 0.1],
           ("jit_step", "all-reduce-start.1"): [2, 0.05],
           ("jit_step", "fusion.7"): [2, 0.15],
           ("jit_step", "fusion.8"): [9, 0.7]}
    trace = Reduced(window_s=1.2, busy_s=1.0, ops=ops, modules={}, gaps=[],
                    chips=4)
    entry = NS(ops={"fusion.7"})
    assert read("collective_share.train",
                run_of(trace=trace, entry=entry)) == pytest.approx(30.0)
    assert read("collective_share.train",
                run_of(trace=trace, entry=NS())) == pytest.approx(15.0)
    quiet = Reduced(window_s=1.0, busy_s=1.0, ops={("m", "fusion.8"):
                                                   [1, 0.5]},
                    modules={}, gaps=[], chips=4)
    assert read("collective_share.train", run_of(trace=quiet)) is None
    assert read("collective_share.train", run_of()) is None


def test_step_host_ms_needs_a_span_per_step():
    rec = Record(attempted=3)
    entry = NS(step_host_s=[0.002, 0.003, 0.004])
    assert read("step_host_ms.train", run_of(record=rec, entry=entry)) \
        == pytest.approx(3.0)
    short = NS(step_host_s=[0.002, 0.003])
    assert read("step_host_ms.train", run_of(record=rec, entry=short)) \
        is None
    assert read("step_host_ms.train", run_of(record=rec)) is None


def test_entry_keeps_each_step_less_its_sync():
    """``settle`` reads the program's spans: each ``train/step`` less the
    ``train/sync`` inside it on its thread."""
    from repro.obs import Span
    from bench.entries import model_train

    spans = [Span("step", "train", 1.0, 0.5, 1),
             Span("sync", "train", 1.1, 0.3, 1),
             Span("sync", "train", 1.2, 0.1, 2),       # another thread
             Span("step", "train", 2.0, 0.2, 1),
             Span("data", "train", 1.9, 0.1, 1)]
    entry = model_train.Entry(QWEN2, {}, 1, NS(traced=True))
    import repro.obs
    tracer = repro.obs.tracer()
    saved = tracer.snapshot
    tracer.snapshot = lambda: spans
    try:
        entry.settle(Record(), {})
    finally:
        tracer.snapshot = saved
    assert entry.step_host_s == pytest.approx([0.2, 0.2])
