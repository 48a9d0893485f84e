"""Entry through ``repro.serve.Engine``: a decoder model served in batches.

The configuration file is the published one; ``program_config`` names the
program's registered configuration, which must have the same widths.  The
weights are made on the device in one jitted call from the seed, in the
published dtype, and handed to the program in its own layout.  The check
runs the plain reference (``bench.refs.qwen3``) over a seeded sample of
the requests the window finished.
"""
from __future__ import annotations

import numpy as np

from bench.harness.core import log
from bench.refs import qwen3 as ref
from bench.traffic import offline_batches


def program_params(w: dict) -> dict:
    """The program's parameter tree from the published weights: layers
    stacked under one scanned group, RMSNorm gains as offsets (which the
    weights already are), and the tied embedding given to the program's
    separate logits matrix."""
    layer = {
        "norm1": w["attn_norm"], "norm2": w["mlp_norm"],
        "attn": {"wq": w["q"], "wk": w["k"], "wv": w["v"], "wo": w["o"],
                 "q_norm": w["q_norm"], "k_norm": w["k_norm"]},
        "ffn": {"w1": w["gate"], "w3": w["up"], "w2": w["down"]},
    }
    return {"embed": w["embed"], "layers": [layer], "tail": [],
            "final_norm": w["final_norm"], "lm_head": w["embed"].T}


def _check_widths(mcfg, cfg: dict) -> None:
    want = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
            "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "d_ff": "intermediate_size", "vocab": "vocab_size",
            "rope_theta": "rope_theta"}
    bad = {k: (getattr(mcfg, k), cfg[v]) for k, v in want.items()
           if getattr(mcfg, k) != cfg[v]}
    if bad or mcfg.pattern != ("attn",) or mcfg.ffn != "swiglu" \
            or not mcfg.qk_norm or mcfg.qkv_bias:
        raise ValueError(f"program config {mcfg.name} differs from the "
                         f"published one: {bad or mcfg}")


class Entry:
    def __init__(self, cfg: dict, mix: dict, seed: int, run):
        self.cfg, self.mix, self.seed, self.run = cfg, mix, seed, run
        self.vocab = cfg["vocab_size"]
        self.engine = None

    def setup(self) -> None:
        import jax
        from repro.configs import get_config
        from repro.serve.engine import Engine, ServeConfig
        mcfg = get_config(self.cfg["program_config"])
        _check_widths(mcfg, self.cfg)
        body = ref.weights_body(self.cfg)
        params = jax.jit(lambda key: program_params(body(key)))(
            ref.seed_key(self.seed))
        self.engine = Engine(mcfg, params,
                             ServeConfig(max_len=self.mix["max_len"]))
        batch = self.mix["batch"]
        for length in sorted(set(self.mix["prompt_lens"])):
            ids = np.zeros((batch, length), np.int32)
            self.engine.generate(ids, 2)

    def generate(self, ids: np.ndarray, new_tokens: int) -> np.ndarray:
        return self.engine.generate(ids, new_tokens)

    def counters(self) -> dict:
        return {}

    def settle(self, rec, counters: dict) -> None:
        pass

    def close(self) -> None:
        self.engine = None

    # -- the check ----------------------------------------------------------
    def sample(self, rec) -> list[tuple[int, int]]:
        """(batch position in the record, row) of the requests checked:
        one of the longest prompt, the rest drawn from the seed; rows
        alternate between the two halves of their batch."""
        n = self.mix["check_requests"]
        rng = np.random.default_rng([int(self.seed), 4])
        rows = self.mix["batch"]
        longest = max(b["prompt"] for b in rec.batches)
        firsts = [k for k, b in enumerate(rec.batches)
                  if b["prompt"] == longest]
        picks = [int(rng.choice(firsts))]
        picks += [int(k) for k in rng.integers(0, len(rec.batches), n - 1)]
        half = max(rows // 2, 1)
        halves = [(0, half), (half, rows)] if rows > 1 else [(0, 1)]
        return [(k, int(rng.integers(*halves[j % len(halves)])))
                for j, k in enumerate(picks)]

    def readings(self, rec, quant: str | None = None) -> np.ndarray:
        """Per served token of the sample, the gap by which the token
        chosen lies below the float32 reference's best logit: the served
        token, or with ``quant`` the token the lower-precision reference
        puts first at the same position (the control)."""
        import jax
        w = ref.make_weights(self.cfg, self.seed)
        new = self.mix["new_tokens"]
        length = max(self.mix["prompt_lens"]) + new - 1
        gaps = []
        for k, row in self.sample(rec):
            b = rec.batches[k]
            ids = offline_batches.prompts(self.seed, b["index"],
                                          self.mix["batch"], b["prompt"],
                                          self.vocab)[row]
            served = np.asarray(b["tokens"][row])
            seq = np.zeros(length, np.int32)
            seq[:b["prompt"]] = ids
            seq[b["prompt"]:b["prompt"] + new - 1] = served[:-1]
            read = np.arange(b["prompt"] - 1, b["prompt"] - 1 + new)
            with jax.default_matmul_precision("highest"):
                logits = ref.logits(w, self.cfg, seq, read)
                if quant is not None:
                    low = ref.logits(w, self.cfg, seq, read, quant=quant)
                    served = np.asarray(low.argmax(-1))
            gaps.append(ref.served_gaps(logits, served))
        return np.concatenate(gaps)

    def control(self, rec) -> dict:
        """The check's numbers with the control in the program's place."""
        lim = self.run.cell["limits"]["logit_gap"]
        gaps = self.readings(rec, lim["control"])
        return {"logit_gap": {"value": float(gaps.max()),
                              "limit": lim["limit"]}}

    def check(self, rec) -> dict:
        gaps = self.readings(rec)
        log(f"check: {len(self.sample(rec))} requests, {gaps.size} served "
            f"tokens; gaps > 0 at {int((gaps > 0).sum())}")
        lim = self.run.cell["limits"]["logit_gap"]["limit"]
        return {"logit_gap": {"value": float(gaps.max()), "limit": lim}}
