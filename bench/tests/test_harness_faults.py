"""Faults of the timed path that the check must catch, each planted in
the program under a tiny cell's run on the CPU: ``correct`` comes out
false."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_fault_model_token_altered(tiny, monkeypatch):
    from repro.serve import engine as E
    real = E.Engine._sample

    def altered(self, logits, key):
        return (real(self, logits, key) + 1) % self.cfg.vocab

    monkeypatch.setattr(E.Engine, "_sample", altered)
    assert tiny.run_cell("model", seed=1).result["correct"] is False


def test_fault_model_step_keeps_its_state(tiny, monkeypatch):
    from repro.models import model as M
    real = M.decode_step

    def stale(params, cfg, cache, tokens):
        logits, _ = real(params, cfg, cache, tokens)
        return logits, cache

    monkeypatch.setattr(M, "decode_step", stale)
    assert tiny.run_cell("model", seed=1).result["correct"] is False


def test_fault_model_half_batch_left_out(tiny, monkeypatch):
    from repro.serve import engine as E
    real = E.Engine.generate

    def half(self, prompts, new_tokens, **kw):
        out = real(self, prompts[:prompts.shape[0] // 2], new_tokens, **kw)
        return np.concatenate([out, out])

    monkeypatch.setattr(E.Engine, "generate", half)
    assert tiny.run_cell("model", seed=1).result["correct"] is False


@pytest.mark.parametrize("kind", ["mm3", "ffn"])
def test_fault_plan_answer_altered(tiny, monkeypatch, kind):
    from repro.serve import engine as E
    real = E.PlanEngine._run_optimized

    def altered(self, *args, **kw):
        out = real(self, *args, **kw)
        return {k: v.at[(0,) * v.ndim].add(1.0 + jnp.max(jnp.abs(v)))
                for k, v in out.items()}

    monkeypatch.setattr(E.PlanEngine, "_run_optimized", altered)
    assert tiny.run_cell(kind, seed=1).result["correct"] is False


def test_fault_plan_half_batch_left_out(tiny, monkeypatch):
    """Every request of a flush gets the first one's answer."""
    from repro.serve import batching

    def first_only(bucket):
        return jax.jit(lambda *leaves: tuple(
            tuple(v[0] for v in leaves) for _ in range(bucket)))

    monkeypatch.setattr(batching, "_make_splitter", first_only)
    mix = dict(tiny.FFN_MIX, rate_per_s=1000, check_requests=10**6)
    monkeypatch.setitem(tiny.CELLS, "ffn",
                        (tiny.FFN, mix, tiny.CELLS["ffn"][2]))
    run = tiny.run_cell("ffn", seed=1)
    assert run.counters["repro_batch_batched_requests_total"] \
        > run.counters["repro_batch_flushes_total"]
    assert run.result["correct"] is False
