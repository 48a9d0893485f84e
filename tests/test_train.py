"""Training substrate: optimizer, data pipeline, checkpointing,
fault-tolerant loop (checkpoint-restart), straggler policy."""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.checkpoint import io as ckpt_io
from repro.configs import get_config
from repro.configs.base import smoke
from repro.data import DataConfig, PrefetchLoader, SyntheticLM
from repro.ft import FailurePlan, InjectedFailure, run_with_restarts
from repro.ft.straggler import StragglerConfig, StragglerMonitor
from repro.train.loop import TrainConfig, train
from repro.train.optimizer import (AdamWConfig, adamw_update,
                                   init_opt_state, lr_schedule)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_matches_manual_single_param():
    cfg = AdamWConfig(lr=1e-2, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                      grad_clip=1e9, warmup_steps=0, total_steps=10**9,
                      min_lr_frac=1.0)
    p = {"w": jnp.array([[1.0, 2.0]])}
    g = {"w": jnp.array([[0.1, -0.2]])}
    st = init_opt_state(p)
    new_p, st2, _ = adamw_update(cfg, p, g, st)
    m = 0.1 * np.array([0.1, -0.2])
    v = 0.01 * np.array([0.1, -0.2]) ** 2
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.99)
    expect = np.array([1.0, 2.0]) - 1e-2 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"])[0], expect, rtol=1e-6)
    assert int(st2.step) == 1


def test_grad_clip_scales_update():
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, warmup_steps=0,
                      weight_decay=0.0, min_lr_frac=1.0)
    p = {"w": jnp.ones((4, 4))}
    g = {"w": jnp.full((4, 4), 100.0)}           # norm = 400
    _, st, metrics = adamw_update(cfg, p, g, init_opt_state(p))
    assert float(metrics["grad_norm"]) == pytest.approx(400.0)
    # clipped: effective grad norm 1.0 -> m = 0.1 * g_clipped
    np.testing.assert_allclose(np.asarray(st.m["w"]),
                               0.1 * 100.0 / 400.0, rtol=1e-5)


def test_lr_schedule_warmup_and_cosine():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                      min_lr_frac=0.1)
    assert float(lr_schedule(cfg, jnp.asarray(0))) == 0.0
    assert float(lr_schedule(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(lr_schedule(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    end = float(lr_schedule(cfg, jnp.asarray(110)))
    assert end == pytest.approx(0.1, rel=1e-3)
    mid = float(lr_schedule(cfg, jnp.asarray(60)))
    assert 0.1 < mid < 1.0


def test_weight_decay_skips_1d_params():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.5, warmup_steps=0,
                      min_lr_frac=1.0, grad_clip=1e9)
    p = {"w2d": jnp.ones((2, 2)), "norm": jnp.ones((2,))}
    g = jax.tree.map(jnp.zeros_like, p)
    new_p, _, _ = adamw_update(cfg, p, g, init_opt_state(p))
    assert float(new_p["w2d"][0, 0]) == pytest.approx(1 - 0.1 * 0.5)
    assert float(new_p["norm"][0]) == 1.0


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_synthetic_data_deterministic_per_step():
    cfg = DataConfig(vocab=64, seq_len=32, global_batch=4, seed=1)
    d1, d2 = SyntheticLM(cfg), SyntheticLM(cfg)
    for step in (0, 3, 17):
        a, la = d1.batch(step)
        b, lb = d2.batch(step)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(la, lb)
    a0, _ = d1.batch(0)
    a1, _ = d1.batch(1)
    assert not np.array_equal(a0, a1)


def test_synthetic_data_labels_are_shifted_tokens():
    cfg = DataConfig(vocab=64, seq_len=32, global_batch=2, seed=0)
    toks, labels = SyntheticLM(cfg).batch(0)
    np.testing.assert_array_equal(toks[:, 1:], labels[:, :-1])


def test_synthetic_data_at_a_real_vocabulary():
    """qwen3's 151936 tokens: the transition is held sparsely (a dense table
    would take 185 GB of host memory) and still mostly follows it."""
    cfg = DataConfig(vocab=151936, seq_len=64, global_batch=4, seed=0)
    src = SyntheticLM(cfg)
    toks, labels = src.batch(0)
    assert toks.min() >= 0 and toks.max() < cfg.vocab
    follows = np.mean([labels[b, t] in src._succ[toks[b, t]]
                       for b in range(4) for t in range(64)])
    assert 0.8 < follows < 0.98


def test_prefetch_loader_order_and_seek():
    cfg = DataConfig(vocab=64, seq_len=8, global_batch=2, seed=0)
    src = SyntheticLM(cfg)
    loader = PrefetchLoader(src)
    try:
        a0 = loader.next()
        a1 = loader.next()
        np.testing.assert_array_equal(a0[0], src.batch(0)[0])
        np.testing.assert_array_equal(a1[0], src.batch(1)[0])
        loader.seek(10)
        a10 = loader.next()
        np.testing.assert_array_equal(a10[0], src.batch(10)[0])
    finally:
        loader.close()


def test_host_sharded_batches_disjoint():
    h0 = SyntheticLM(DataConfig(vocab=32, seq_len=8, global_batch=4,
                                seed=5, n_hosts=2, host_id=0))
    h1 = SyntheticLM(DataConfig(vocab=32, seq_len=8, global_batch=4,
                                seed=5, n_hosts=2, host_id=1))
    assert h0.cfg.host_batch == 2
    a, _ = h0.batch(0)
    b, _ = h1.batch(0)
    assert a.shape == (2, 8)
    assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "nest": {"b": jnp.ones((4,), jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}
    ckpt_io.save(str(tmp_path), 3, tree)
    like = jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), tree)
    back = ckpt_io.restore(str(tmp_path), 3, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_no_commit_is_invisible(tmp_path):
    tree = {"a": jnp.ones((2,))}
    path = ckpt_io.save(str(tmp_path), 1, tree)
    os.remove(os.path.join(path, "COMMIT"))
    assert ckpt_io.latest_step(str(tmp_path)) is None


def test_checkpoint_manager_rotation(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for step in (1, 2, 3, 4):
        mgr.save(step, {"x": jnp.asarray([step])})
    assert mgr.latest_step() == 4
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_00000003", "step_00000004"]


def test_checkpoint_async_save_visible_after_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    mgr.save(5, {"x": jnp.arange(3)})
    mgr.wait()
    back, step = mgr.restore({"x": np.zeros(3, np.int32)})
    assert step == 5
    np.testing.assert_array_equal(np.asarray(back["x"]), [0, 1, 2])


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------
def test_run_with_restarts_replays_from_checkpoint():
    saves: dict[int, int] = {}

    def step_fn(state, step):
        plan.maybe_fail(step)
        return state + 1

    def save_fn(state, step):
        saves[step] = state

    def restore_fn():
        if not saves:
            return None, None
        step = max(saves)
        return saves[step], step

    plan = FailurePlan(at_steps=(7,))
    final, stats = run_with_restarts(
        total_steps=10, state=0, step_fn=step_fn, save_fn=save_fn,
        restore_fn=restore_fn, checkpoint_every=5, max_restarts=3,
        failure_plan=plan)
    assert final == 10                       # every step executed
    assert stats.restarts == 1
    assert stats.replayed_steps == 2         # steps 5,6 replayed


def test_run_with_restarts_budget_exhausted():
    def step_fn(state, step):
        raise InjectedFailure("always")

    with pytest.raises(InjectedFailure):
        run_with_restarts(
            total_steps=3, state=0, step_fn=step_fn,
            save_fn=lambda s, t: None, restore_fn=lambda: (None, None),
            checkpoint_every=1, max_restarts=2)


def test_training_loop_end_to_end_with_injected_failure(tmp_path):
    """Loss decreases AND an injected mid-run failure is absorbed by
    checkpoint-restart with identical final history (determinism)."""
    cfg = dataclasses.replace(smoke(get_config("qwen1.5-0.5b")),
                              n_layers=2, remat=False)
    tc = TrainConfig(total_steps=16, checkpoint_every=5,
                     checkpoint_dir=str(tmp_path / "ck1"),
                     global_batch=4, seq_len=32, log_every=100)
    opt_cfg = AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=16)
    _, hist_clean, stats_clean = train(cfg, tc, opt_cfg=opt_cfg)
    assert stats_clean.restarts == 0
    losses = [l for _, l in hist_clean]
    assert min(losses[-4:]) < losses[0], "loss must decrease"

    tc2 = dataclasses.replace(tc, checkpoint_dir=str(tmp_path / "ck2"))
    _, hist_fail, stats_fail = train(
        cfg, tc2, opt_cfg=opt_cfg, failure_plan=FailurePlan(at_steps=(7,)))
    assert stats_fail.restarts == 1
    # deterministic replay: the last executed step matches the clean run
    clean = dict(hist_clean)
    fail = dict(hist_fail)
    assert fail[15] == pytest.approx(clean[15], rel=1e-5)


# ---------------------------------------------------------------------------
# straggler policy
# ---------------------------------------------------------------------------
def test_straggler_flagged_after_patience():
    mon = StragglerMonitor(4, StragglerConfig(threshold=1.5, patience=3,
                                              min_steps=2))
    flagged = []
    for step in range(10):
        times = [1.0, 1.0, 1.0, 1.0]
        if step >= 4:
            times[2] = 3.0                   # host 2 goes slow
        flagged = mon.observe(times)
        if flagged:
            break
    assert flagged == [2]
    shares = mon.demote(2)
    assert set(shares) == {0, 1, 3}
    assert sum(shares.values()) == pytest.approx(1.0)


def test_straggler_transient_blip_not_flagged():
    mon = StragglerMonitor(2, StragglerConfig(threshold=1.5, patience=3,
                                              min_steps=2))
    for step in range(10):
        times = [1.0, 3.0 if step == 5 else 1.0]   # single blip
        assert mon.observe(times) == []


def test_decay_mask_spares_stacked_norms_and_biases():
    """Weight decay goes to the matrices: the scanned layers' norm gains
    and biases carry a leading layer axis and are still not decayed."""
    from repro.models import model as M
    cfg = smoke(get_config("qwen1.5-32b"))
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    mask = M.decay_mask(shapes)
    layer = mask["layers"][0]
    assert shapes["layers"][0]["norm1"].ndim == 2
    assert not layer["norm1"] and not layer["attn"]["bq"]
    assert layer["attn"]["wq"] and layer["ffn"]["w2"]
    assert mask["embed"] and mask["lm_head"] and not mask["final_norm"]


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------
def test_trainer_step_is_the_train_step():
    """``Trainer.load`` + ``Trainer.step`` compute what the plain
    ``train_step`` computes, and count the step and its tokens."""
    from repro.models import model as M
    from repro.obs import default_registry
    from repro.train.loop import Trainer
    from repro.train.train_step import train_step
    cfg = dataclasses.replace(smoke(get_config("qwen1.5-32b")), n_layers=2,
                              compute_dtype="float32")
    opt_cfg = AdamWConfig(warmup_steps=1, total_steps=10)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                         cfg.vocab), np.int32)
    labels = np.roll(toks, -1, axis=1)
    p1, o1, m1 = train_step(params, init_opt_state(params), toks, labels,
                            cfg=cfg, opt_cfg=opt_cfg)
    reg = default_registry()
    before = reg.value("repro_train_tokens_total")
    trainer = Trainer(cfg, global_batch=4, seq_len=16, opt_cfg=opt_cfg)
    trainer.load(params)
    m2 = trainer.step(toks, labels)
    assert m2["loss"] == pytest.approx(float(m1["loss"]), rel=1e-6)
    assert m2["grad_norm"] == pytest.approx(float(m1["grad_norm"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(trainer.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert reg.value("repro_train_tokens_total") - before == 64


def test_trainer_state_is_sharded_from_the_first_byte():
    """On a 2x2 mesh of four fake devices, the state the trainer makes
    holds at most a quarter of any 2-D weight (and of its Adam moments)
    on each device; a restore template allocates nothing."""
    from conftest import run_subprocess
    r = run_subprocess("""
import jax, numpy as np
from repro.configs import get_config
from repro.configs.base import smoke
from repro.launch.mesh import make_mesh
from repro.train.loop import Trainer
cfg = smoke(get_config('qwen1.5-32b'))
mesh = make_mesh((2, 2), ('data', 'model'))
t = Trainer(cfg, global_batch=4, seq_len=16, mesh=mesh)
t.init(jax.random.PRNGKey(0))
trees = [t.params, t.opt_state.m, t.opt_state.v]
n = 0
for tree in trees:
    for x in jax.tree.leaves(tree):
        if x.ndim - (x.shape[0] == cfg.n_layers) < 2:
            continue
        for shard in x.addressable_shards:
            assert 4 * shard.data.size <= x.size, (x.shape, x.sharding)
        n += 1
shapes, shardings = t.template()
assert all(isinstance(x, jax.ShapeDtypeStruct)
           for x in jax.tree.leaves(shapes))
print('OK', n)
""", n_devices=4, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split()[0] == "OK" and int(r.stdout.split()[1]) >= 15
