"""Entry through ``repro.train.Trainer``: a decoder trained step by step on
a mesh of the cell's chips.

The configuration file holds the published keys, two of them cut
(``num_hidden_layers``, ``vocab_size``, each listed in ``reduced`` with the
published value under ``published``); ``program_config`` names the
program's registered configuration, which must have the published widths,
and ``program`` the mesh, the microbatches and the AdamW settings it trains
with.  The weights are made on the devices from the seed, in the program's
sharding, by the generator the reference regenerates them with
(``bench.refs.qwen2.weight``).  Set-up compiles and warms one step, then
makes the state anew from the seed, so the window's first step starts from
the seeded state.

The check is that first step.  Set-up reads a seeded sample of every
tensor's entries before it; after it the entry dispatches small jitted
gathers of the same entries of the parameters and of Adam's two moments
and keeps them on the device.  After the window it compares them with the
plain reference (``bench.refs.qwen2``) on the same batch:

- ``grad_err``: the gradient read through the first moment,
  ``g = m / ((1 - b1) * min(1, clip / |g_ref|))`` with the reference's
  norm, against the reference's gradient: per tensor the largest
  difference over the largest reference entry, the largest over tensors.
- ``update_err``: the parameters' change and the second moment against
  AdamW's first step (``bench.refs.qwen2.adamw_first_step``) at the
  clipped gradient that ``m`` holds: per tensor the norm of the difference
  over the norm of AdamW's, the largest over tensors.  A state left
  unchanged reads 1.  At the first step AdamW moves each entry by about
  ``lr * sign(g)``, so the rule is applied to the program's own ``m``
  (which ``grad_err`` holds to the reference): at the reference's
  gradient, entries within rounding of zero would flip sign.
- ``decay_err``: the same difference of the parameters' change over what
  weight decay takes from them (``lr * weight_decay * p``, a few float32
  spacings of ``p``), over every tensor not all zero.  A step that skips
  the decay of a matrix, or decays a bias, reads about 1; the rounding of
  the new parameters reads well under it.

The loss is logged beside the reference's; at initialisation it sits near
``ln(vocab)`` at any precision, so it separates nothing and is not judged.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.harness.core import log
from bench.refs import qwen2 as ref
from bench.refs.qwen3 import seed_key
from bench.traffic import train_steps

#: the reference's per-layer tensors -> the program's stacked ones
PROGRAM = {"attn_norm": ("norm1",), "mlp_norm": ("norm2",),
           "q": ("attn", "wq"), "k": ("attn", "wk"), "v": ("attn", "wv"),
           "o": ("attn", "wo"), "q_bias": ("attn", "bq"),
           "k_bias": ("attn", "bk"), "v_bias": ("attn", "bv"),
           "gate": ("ffn", "w1"), "up": ("ffn", "w3"), "down": ("ffn", "w2")}
TOP = ("embed", "final_norm", "lm_head")

#: collective operations, as the compiled program names them
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def program_params(cfg: dict):
    """``key -> the program's parameter tree``: every tensor the
    reference's ``weight``, the layers stacked under one scanned group."""
    import jax.numpy as jnp
    shp = ref.shapes(cfg)
    n = cfg["num_hidden_layers"]

    def make(key):
        def stack(base):
            return jnp.stack([ref.weight(key, f"layers.{i}.{base}",
                                         shp[f"layers.{i}.{base}"])
                              for i in range(n)])
        layer: dict = {}
        for base, path in PROGRAM.items():
            node = layer
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = stack(base)
        out = {k: ref.weight(key, k, shp[k]) for k in TOP}
        return {**out, "layers": [layer], "tail": []}

    return make


def program_config(cfg: dict):
    """The registered configuration cut as the file says; its widths must
    be the published ones."""
    from repro.configs import get_config
    mcfg = get_config(cfg["program_config"])
    d, f, hq, hkv, hd, n, v = ref.dims(cfg)
    pub = cfg["published"]
    want = {"n_layers": pub["num_hidden_layers"], "d_model": d,
            "n_heads": hq, "n_kv_heads": hkv, "head_dim": hd, "d_ff": f,
            "vocab": pub["vocab_size"], "rope_theta": cfg["rope_theta"],
            "q_heads": hq, "kv_heads": hkv}
    bad = {k: (getattr(mcfg, k), w) for k, w in want.items()
           if getattr(mcfg, k) != w}
    if bad or mcfg.pattern != ("attn",) or mcfg.ffn != "swiglu" \
            or not mcfg.qkv_bias or mcfg.qk_norm:
        raise ValueError(f"program config {mcfg.name} differs from the "
                         f"published one: {bad or mcfg}")
    return dataclasses.replace(mcfg, n_layers=n, vocab=v)


def sample_entries(cfg: dict, seed: int, k: int) -> dict:
    """``k`` seeded entries of every tensor: {reference name: (k, ndim)}."""
    rng = np.random.default_rng([int(seed), 6])
    out = {}
    for name, shape in ref.shapes(cfg).items():
        flat = rng.integers(0, int(np.prod(shape)), size=k)
        out[name] = np.stack(np.unravel_index(flat, shape), axis=1)
    return out


def _program_indices(cfg: dict, entries: dict) -> dict:
    """The entries as indices into the program's tensors, by path."""
    n = cfg["num_hidden_layers"]
    out = {k: entries[k] for k in TOP}
    for base, path in PROGRAM.items():
        out["/".join(("layers", "0") + path)] = np.concatenate([
            np.concatenate([np.full((len(entries[f"layers.{i}.{base}"]), 1),
                                    i), entries[f"layers.{i}.{base}"]], 1)
            for i in range(n)])
    return out


def _key(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def sampler(mesh, shardings, indices: dict):
    """A jitted ``tree -> {path: values}`` that gathers ``indices`` from a
    tree laid out as ``shardings``: each device reads the entries it
    holds, and one sum over the axes a tensor is split on assembles them,
    so no tensor is gathered whole."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def one(x, idx, spec):
        spec = tuple(spec) + (None,) * (x.ndim - len(tuple(spec)))
        axes = tuple(a for s in spec if s is not None
                     for a in ((s,) if isinstance(s, str) else s))

        def local(xl):
            rel, inside = [], True
            for d, s in enumerate(spec):
                off = 0 if s is None else jax.lax.axis_index(s) * xl.shape[d]
                r = jnp.asarray(idx[:, d]) - off
                inside = inside & (r >= 0) & (r < xl.shape[d])
                rel.append(jnp.clip(r, 0, xl.shape[d] - 1))
            vals = jnp.where(inside, xl[tuple(rel)], 0.0)
            return jax.lax.psum(vals, axes) if axes else vals

        return jax.shard_map(local, mesh=mesh, in_specs=(P(*spec),),
                             out_specs=P())(x)

    def gather(tree):
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        specs = dict((_key(p), s.spec) for p, s in
                     jax.tree_util.tree_flatten_with_path(shardings)[0])
        return {_key(p): one(x, indices[_key(p)], specs[_key(p)])
                for p, x in leaves if _key(p) in indices}

    return jax.jit(gather)


class Entry:
    def __init__(self, cfg: dict, mix: dict, seed: int, run):
        self.cfg, self.mix, self.seed, self.run = cfg, mix, seed, run
        self.vocab = cfg["vocab_size"]
        self.trainer = None
        self.first = None            # the window's first step: metrics
        self.before = None           # the sampled parameters before it
        self.after = None            # ... and params, m, v after it
        self.step_host_s: list = []  # per step: host time less the sync
        self.ops: set = set()        # the step's collective operations
        self._ref = None             # the reference's loss, gradient, norm

    def setup(self) -> None:
        import jax
        from repro.launch.mesh import make_mesh
        from repro.obs import tracer
        from repro.train.loop import Trainer
        from repro.train.optimizer import AdamWConfig
        mcfg = program_config(self.cfg)
        prog = self.cfg["program"]
        shape = (prog["mesh"]["data"], prog["mesh"]["model"])
        mesh = make_mesh(shape, ("data", "model"), self.run.device["used"])
        self.trainer = Trainer(mcfg, global_batch=self.mix["global_batch"],
                               seq_len=self.mix["seq_len"], mesh=mesh,
                               opt_cfg=AdamWConfig(**prog["optimizer"]),
                               microbatches=prog["microbatches"])
        self.entries = sample_entries(self.cfg, self.seed,
                                      self.mix["check_entries"])
        # params, m and v share their shapes and shardings: one sampler
        self._sample = sampler(mesh, self.trainer.shardings["params"],
                               _program_indices(self.cfg, self.entries))
        self._make = program_params(self.cfg)
        key = seed_key(self.seed)
        self.trainer.init(key, self._make)
        rows, length = self.mix["global_batch"], self.mix["seq_len"]
        warm = train_steps.batch(self.seed, 0, rows, length, self.vocab)
        m = self.trainer.step(*warm)
        jax.block_until_ready(self._sample(self.trainer.opt_state.m))
        log(f"warm step: loss {m['loss']:.4f}, grad_norm "
            f"{m['grad_norm']:.4f}")
        ma = self.trainer.compiled().memory_analysis()
        if ma is not None:
            log(f"step program per device: arguments "
                f"{ma.argument_size_in_bytes}, temporaries "
                f"{ma.temp_size_in_bytes} bytes")
        self.trainer.init(key, self._make)
        self.before = {k: np.asarray(v) for k, v in
                       self._sample(self.trainer.params).items()}
        if self.run.traced:
            tracer().enabled = True
        tracer().clear()

    def step(self, tokens, labels) -> float:
        m = self.trainer.step(tokens, labels)
        if self.first is None:
            self.first = m
            t = self.trainer
            self.after = {"p": self._sample(t.params),
                          "m": self._sample(t.opt_state.m),
                          "v": self._sample(t.opt_state.v)}
        return m["loss"]

    def counters(self) -> dict:
        from repro.obs import default_registry
        reg = default_registry()
        return {k: reg.value(k) for k in ("repro_train_steps_total",
                                          "repro_train_tokens_total")}

    def settle(self, rec, counters: dict) -> None:
        from repro.obs import tracer
        spans = [sp for sp in tracer().snapshot() if sp.cat == "train"]
        syncs = [sp for sp in spans if sp.name == "sync"]
        for sp in spans:
            if sp.name != "step":
                continue
            end = sp.start_s + sp.dur_s
            inside = sum(min(s.start_s + s.dur_s, end)
                         - max(s.start_s, sp.start_s)
                         for s in syncs if s.tid == sp.tid
                         and s.start_s < end and s.start_s + s.dur_s
                         > sp.start_s)
            self.step_host_s.append(sp.dur_s - inside)

    def collective_ops(self) -> set:
        """Names of the step's operations that are, or call, a
        collective: a reduce-scatter is compiled as a fusion that calls
        one."""
        import re
        text = self.trainer.compiled().as_text() if self.trainer else ""
        pat = "|".join(COLLECTIVES)
        return set(re.findall(
            rf"%([\w.-]+) = [^\n]*?(?:\b(?:{pat})(?:-start|-done)?\(|"
            rf"calls=%(?:{pat}))", text))

    def close(self) -> None:
        if self.run.traced:
            self.ops = self.collective_ops()
        self.trainer = None
        if self.after is not None:
            self.after = {part: {k: np.asarray(v) for k, v in tree.items()}
                          for part, tree in self.after.items()}

    # -- the check ----------------------------------------------------------
    def _by_name(self, sample: dict) -> dict:
        """A sample keyed by program path, by the reference's names."""
        n = self.cfg["num_hidden_layers"]
        k = self.mix["check_entries"]
        out = {name: np.asarray(sample[name], np.float64) for name in TOP}
        for base, path in PROGRAM.items():
            got = np.asarray(sample["/".join(("layers", "0") + path)],
                             np.float64)
            for i in range(n):
                out[f"layers.{i}.{base}"] = got[i * k:(i + 1) * k]
        return out

    def _reference(self, quant: str | None = None):
        import jax
        rows, length = self.mix["global_batch"], self.mix["seq_len"]
        tokens, labels = train_steps.batch(self.seed, 0, rows, length,
                                           self.vocab)
        with jax.default_matmul_precision("highest"):
            return ref.loss_and_grads(self.cfg, self.seed, tokens, labels,
                                      self.entries, quant=quant,
                                      devices=self.run.device["used"])

    @staticmethod
    def _grad_err(got: dict, want: dict) -> tuple[float, str]:
        errs = {name: float(np.max(np.abs(got[name] - want[name]))
                            / max(np.max(np.abs(want[name])), 1e-30))
                for name in want}
        worst = max(errs, key=errs.get)
        return errs[worst], worst

    def readings(self) -> dict:
        """``grad_err``, ``update_err`` and ``decay_err`` of the program's
        first step."""
        if self._ref is None:
            self._ref = self._reference()
        loss, g_ref, norm = self._ref
        opt = self.cfg["program"]["optimizer"]
        clip = min(1.0, opt["grad_clip"] / max(norm, 1e-30))
        m = self._by_name(self.after["m"])
        v, p1 = self._by_name(self.after["v"]), self._by_name(self.after["p"])
        p0 = self._by_name(self.before)
        grad_err, worst = self._grad_err(
            {k: x / ((1 - opt["b1"]) * clip) for k, x in m.items()}, g_ref)
        shapes = ref.shapes(self.cfg)
        decay = ref.first_lr(opt) * opt["weight_decay"]
        upd, dec = {}, {}
        for name in g_ref:
            v_want, dp_want = ref.adamw_first_step(
                opt, m[name] / (1 - opt["b1"]), p0[name],
                decays=len(shapes[name]) >= 2)
            miss = float(np.linalg.norm(p1[name] - p0[name] - dp_want))
            upd[name] = max(
                miss / max(float(np.linalg.norm(dp_want)), 1e-30),
                float(np.linalg.norm(v[name] - v_want))
                / max(float(np.linalg.norm(v_want)), 1e-30))
            if np.any(p0[name]):
                dec[name] = miss / (decay * float(np.linalg.norm(p0[name])))
        upd_worst = max(upd, key=upd.get)
        dec_worst = max(dec, key=dec.get)
        log(f"check: loss {self.first['loss']:.6f} vs reference {loss:.6f} "
            f"(gap {abs(self.first['loss'] - loss) / abs(loss):.3e}, not "
            f"judged); grad_norm {self.first['grad_norm']:.6f} vs "
            f"{norm:.6f}; widest gradient error {grad_err:.3e} in {worst}; "
            f"widest update error {upd[upd_worst]:.3e} in {upd_worst}; "
            f"widest decay error {dec[dec_worst]:.3e} in {dec_worst}")
        return {"grad_err": grad_err, "update_err": upd[upd_worst],
                "decay_err": dec[dec_worst]}

    def _judged(self, values: dict) -> dict:
        lim = self.run.cell["limits"]
        return {k: {"value": v, "limit": lim[k]["limit"]}
                for k, v in values.items()}

    def control(self, rec) -> dict:
        """``grad_err`` with the control, the reference one precision
        lower, in the program's place (``update_err`` and ``decay_err``
        apply AdamW to the program's own moment, so they have none)."""
        quant = self.run.cell["limits"]["grad_err"]["control"]
        if self._ref is None:
            self._ref = self._reference()
        _, got, _ = self._reference(quant)
        err, worst = self._grad_err(got, self._ref[1])
        log(f"control {quant}: widest gradient error {err:.3e} in {worst}")
        return self._judged({"grad_err": err})

    def check(self, rec) -> dict:
        if self.first is None:
            return {}
        return self._judged(self.readings())
