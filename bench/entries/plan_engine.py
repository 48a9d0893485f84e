"""Entry through ``repro.serve.PlanEngine``: plans served by the compiler.

Two kinds of configuration, by their ``kind`` key:

* ``polybench``: a PolyBench kernel's task graph, solved with the
  configuration's solver options and registered with
  ``PlanEngine.register``; requests are dicts of input arrays made on the
  device from the seed (``input_sets`` of them, cycled).
* ``swiglu_ffn``: the SwiGLU FFN block, traced and solved through
  ``PlanEngine.register_function``; each request carries a host ``x`` from
  a pool made from the seed, the weights stay on the device.

The plan store and the compile cache live at fixed paths inside the
checkout, so only a checkout's first run solves and compiles.  The check
compares a seeded sample of the window's answers with a plain float32
reference at ``precision=highest``.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness.core import log, state_dir
from bench.harness.sample import Reservoir
from bench.refs import plans as ref

#: Requests each entry sees in warm-up: the program's cost-model drift
#: detector needs 16 x 12 = 192 before it may fire, and a re-solve it
#: fires has to finish before the window opens.
DRIFT_WARMUP = 192


def counter_totals(registry) -> dict:
    """Every counter of the engine's registry, summed over its labels."""
    out = {}
    for name, fam in registry.snapshot().items():
        if fam["kind"] == "counter":
            out[name] = sum(fam["values"].values())
    return out


class Entry:
    def __init__(self, cfg: dict, mix: dict, seed: int, run):
        self.cfg, self.mix, self.seed, self.run = cfg, mix, seed, run
        self.name = cfg["kind"]
        self.engine = None
        self.plan_latency_s = None
        self.keep = Reservoir(seed, mix["check_requests"])

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        from repro.core import SolverOptions
        from repro.serve.batching import BatchConfig
        from repro.serve.engine import PlanEngine, ServeConfig
        batching = self.cfg.get("batching")
        sc = ServeConfig(plan_store_dir=state_dir("plans"),
                         batching=None if batching is None
                         else BatchConfig(**batching))
        self.engine = PlanEngine(sc=sc)
        opts = SolverOptions(**self.cfg["solver"])
        t0 = time.perf_counter()
        if self.cfg["kind"] == "polybench":
            self._setup_graph(opts)
        elif self.cfg["kind"] == "swiglu_ffn":
            self._setup_function(opts)
        else:
            raise ValueError(f"unknown plan kind {self.cfg['kind']!r}")
        log(f"setup: registered {self.name} in "
            f"{time.perf_counter() - t0:.3f}s; predicted latency "
            f"{self.plan_latency_s!r} s")
        self._warm()

    def _setup_graph(self, opts) -> None:
        from repro.core import solve
        from repro.core.polybench import BUILDERS
        shapes = {k: self.cfg[k] for k in self.cfg["extents"]}
        graph = BUILDERS[self.cfg["kernel"]](**shapes)
        self.inputs = ref.graph_inputs(self.cfg, self.mix, self.seed)
        plan = solve(graph, None, opts)
        log(f"setup: plan from store: {bool(getattr(plan, 'store_hit', 0))}"
            f", {plan.n_evaluated} evaluations, solver "
            f"{plan.solver_seconds:.3f}s")
        self.plan_latency_s = plan.latency_s
        self.engine.register(self.name, graph, plan)

    def _setup_function(self, opts) -> None:
        import jax
        w, xs = ref.ffn_data(self.cfg, self.mix, self.seed)
        self.weights = w
        self.x_host = [np.asarray(x) for x in xs]
        del xs
        example = (jax.numpy.asarray(self.x_host[0]),) + tuple(w)
        tf = self.engine.register_function(self.name, ref.swiglu_ffn,
                                           example, solver_opts=opts)
        if tf is None:
            raise RuntimeError(f"{self.name}: trace/solve failed at "
                               "registration")
        # registration pre-solves the bucket ladder on a thread of its
        # own; a bucket registered meanwhile from here races it for the
        # same plan-store file, so wait for it to finish
        deadline = time.monotonic() + 120.0
        while not counter_totals(self.engine.metrics).get(
                "repro_buckets_presolved_total", 0) \
                and time.monotonic() < deadline:
            time.sleep(0.05)

    def _request_args(self, i: int):
        if self.cfg["kind"] == "polybench":
            return self.inputs[i % len(self.inputs)]
        return (self.x_host[i % len(self.x_host)],) + tuple(self.weights)

    def _warm(self) -> None:
        """Every entry the window will use gets ``DRIFT_WARMUP`` requests;
        any re-solve the drift detector fires must end before the window
        opens."""
        import jax
        eng = self.engine
        if self.cfg.get("batching") is None:
            for i in range(DRIFT_WARMUP):
                jax.block_until_ready(
                    eng.submit_async(self.name, self._request_args(i))
                    .result())
        else:
            from repro.serve.batching import BATCH_SEP
            eng.batcher().warmup(self.name)
            x0 = self._request_args(0)
            for b in eng.batcher().buckets:
                args = tuple(jax.numpy.stack([jax.numpy.asarray(a)] * b)
                             for a in x0)
                for _ in range(DRIFT_WARMUP):
                    out = eng.submit(f"{self.name}{BATCH_SEP}{b}", args)
                jax.block_until_ready(out)
                del args
        deadline = time.monotonic() + 120.0
        while True:
            c = counter_totals(eng.metrics)
            fired = c.get("repro_drift_triggers_total", 0)
            done = c.get("repro_plan_refreshes_total", 0)
            if done >= fired or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        log(f"setup: warm-up drift triggers {fired}, plan refreshes {done}")

    # -- the window ---------------------------------------------------------
    def request(self, i: int):
        """Closed loop: send request ``i`` and wait until it is ready."""
        import jax
        out = self.engine.submit_async(self.name, self._request_args(i))
        out = jax.block_until_ready(out.result())
        self.keep.offer(i, out)
        return out

    def send(self, i: int):
        """Open loop: send request ``i``; returns its future."""
        return self.engine.submit_async(self.name, self._request_args(i))

    def wait(self, i: int, fut, timeout: float):
        import jax
        out = jax.block_until_ready(fut.result(timeout=timeout))
        self.keep.offer(i, out)
        return out

    def counters(self) -> dict:
        return counter_totals(self.engine.metrics)

    def settle(self, rec, counters: dict) -> None:
        """Requests the plain-jit fallback served were not served by the
        compiler: they count as failed."""
        key = ("repro_batch_fallbacks_total" if self.cfg.get("batching")
               else "repro_entry_fallbacks_total")
        fallbacks = counters.get(key, 0)
        rec.failed += fallbacks
        rec.optimized = max(rec.completed - fallbacks, 0)
        rec.kept = self.keep.items
        log(f"window: drift triggers "
            f"{counters.get('repro_drift_triggers_total', 0)}, plan "
            f"refreshes {counters.get('repro_plan_refreshes_total', 0)}")

    def close(self) -> None:
        if self.engine is not None:
            self.engine.shutdown()
        self.engine = None
        self.inputs = self.weights = self.x_host = None

    # -- the check ----------------------------------------------------------
    def readings(self, rec, control: str | None = None) -> list[float]:
        """max |answer - reference| / max |reference| of each sampled
        answer.  With ``control`` (``"high"`` for a float32 graph, ``"fp8"``
        for the bf16 block) the reference computed one precision lower
        takes the answer's place."""
        graph = self.cfg["kind"] == "polybench"
        if graph:
            sets = ref.graph_inputs(self.cfg, self.mix, self.seed)
        else:
            w, xs = ref.ffn_data(self.cfg, self.mix, self.seed)
        out = []
        for i in sorted(rec.kept):
            got = rec.kept[i]
            if graph:
                ins = sets[i % len(sets)]
                want = ref.mm3(ins)
                if control:
                    got = ref.mm3(ins, precision=control)
                pairs = [(got[k], want[k]) for k in got]
            else:
                x = xs[i % len(xs)]
                want = ref.swiglu_ffn_f32(x, *w)
                if control:
                    got = ref.swiglu_ffn_f32(x, *w, quant=control)
                pairs = [(got, want)]
            out.append(max(ref.rel_err(g, v) for g, v in pairs))
        return out

    def control(self, rec) -> dict:
        """The check's numbers with the control in the program's place."""
        lim = self.run.cell["limits"]["rel_err"]
        return {"rel_err": {"value": max(self.readings(rec, lim["control"])),
                            "limit": lim["limit"]}}

    def check(self, rec) -> dict:
        errs = self.readings(rec)
        log(f"check: {len(errs)} sampled answers of {rec.completed}")
        lim = self.run.cell["limits"]["rel_err"]["limit"]
        value = max(errs) if errs else float("inf")
        return {"rel_err": {"value": value, "limit": lim}}
