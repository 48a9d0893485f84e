"""Batched serving engines: LM decode loop + plan-execution serving.

``Engine`` is the static-shape LM batch engine (the TPU-friendly design):
fixed batch slots, fixed max length, jitted prefill/decode steps.
Continuous batching is approximated at the slot level — finished sequences
are replaced between decode bursts (slot recycling), which is what
production TPU servers do between jitted macro-steps.

``PlanEngine`` is the dataflow-plan counterpart: it serves repeated
executions of solved plans through the whole-plan compiled-program cache
(`repro.codegen.program`), so after the first request for a (graph, plan,
impl) triple every subsequent request — including from a *new* PlanEngine —
hits a fully compiled program with zero re-lowering or re-tracing.

Workloads need not be hand-modeled graphs: ``register_function`` traces an
arbitrary JAX callable through ``repro.frontend``, solves it, and serves it
through the same cache/pool/warmup path — requests for function entries
pass positional-argument tuples instead of array dicts and get the
function's own result pytree back.

With ``ServeConfig.batching`` set, ``submit_async`` adds true continuous
batching *above* ``submit``: a bounded queue drained by one background
batcher thread coalesces same-entry requests into power-of-two buckets
served by batched re-traces (``repro.serve.batching``), so the steady-state
cost of a bucket-``B`` flush is one dispatch instead of ``B``.

Fault tolerance (the ``repro.ft`` contract): the request path never
*assumes* success.  Admission control bounds the in-flight depth
(:class:`~repro.ft.EngineOverloaded` backpressure) and enforces per-submit
deadline budgets; any failure in trace/solve/compile/execute — including
miscompiles caught by sampled canary validation against the plain-jit
oracle and NaN/inf output guards — degrades that request to the plain-jit
fallback path, quarantines the entry behind a per-entry circuit breaker,
and re-solves in the background with exponential backoff.  A
:class:`~repro.ft.ChaosPlan` in ``ServeConfig.chaos`` deterministically
injects every one of those failures for tests and
``benchmarks/bench_chaos.py``.  The happy path stays one dispatch: with a
closed breaker and no chaos configured the additions are a dict lookup
and two branch checks.
"""
from __future__ import annotations

import dataclasses
import itertools
import logging
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ft.serve import (BreakerState, ChaosPlan, CircuitBreaker,
                        DeadlineExceeded, EngineOverloaded, MiscompileError)
from ..ft.straggler import StragglerConfig, StragglerMonitor
from ..models import model as M
from ..obs import (Counter, DriftConfig, DriftDetector, MetricsRegistry,
                   configure_logging, default_registry)
from ..obs import tracer as _obs_tracer
from .batching import BATCH_SEP, BatchConfig, Batcher

log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512
    temperature: float = 0.0       # 0 = greedy
    eos_id: int | None = None
    seed: int = 0
    # -- plan-serving knobs (PlanEngine) ----------------------------------
    # Persistent plan store directory (repro.store): replicas pointed at
    # the same path share *solved plans* across processes, so a fresh
    # replica's register_function loads a fingerprint-keyed plan instead
    # of running the solver sweep.  A plan priced for an older hardware
    # profile (calibration drift) is still served immediately and
    # re-solved in the background.  (env: REPRO_PLAN_STORE_DIR)
    plan_store_dir: str | None = None
    # Bound of the process-wide compiled-program LRU cache; None keeps the
    # current global setting.  (env equivalent: REPRO_PROGRAM_CACHE_SIZE)
    program_cache_size: int | None = None
    # Round-robin executable-pool size per cached program; None defers to
    # REPRO_PROGRAM_POOL_SIZE (default 1).
    pool_size: int | None = None
    # Admission policy: max (graph, plan) pairs registered at once; the
    # least-recently-used registration is evicted past this.  None = no cap.
    max_plans: int | None = None
    # -- resilience knobs (PlanEngine) ------------------------------------
    # Default per-submit deadline budget in seconds (None = unbounded).  A
    # request that cannot be admitted before its budget expires is rejected
    # with DeadlineExceeded; one that finishes late counts a deadline miss.
    deadline_s: float | None = None
    # Bounded in-flight depth: at most this many submits execute at once;
    # excess callers wait up to admission_timeout_s (backpressure) and are
    # then rejected with EngineOverloaded.  None = unbounded.
    max_inflight: int | None = None
    admission_timeout_s: float = 0.1
    # Sampled canary validation: every Nth optimized execution per entry is
    # synchronously validated against the plain-jit oracle (jax.jit(fn)
    # for function entries, the statement reference oracle for graphs); a
    # mismatch is a miscompile -> immediate quarantine + fallback.  0 = off
    # (the happy path stays one asynchronous dispatch).
    canary_every: int = 0
    # NaN/inf output guard: "canary" checks finiteness on canary-sampled
    # requests, "always" on every request (forces a device sync per
    # submit), "off" never.
    nan_guard: str = "canary"
    # Graceful degradation: failures fall back to the plain-jit path for
    # that request instead of raising.  False re-raises (debugging).
    fallback: bool = True
    # Per-entry circuit breaker: this many consecutive optimized-path
    # failures quarantine the entry (every request falls back); after
    # breaker_reset_s one probe request tries the optimized path again.
    breaker_threshold: int = 3
    breaker_reset_s: float = 30.0
    # Background re-solve backoff schedule for quarantined entries.
    resolve_backoff_s: float = 0.05
    resolve_backoff_mult: float = 2.0
    resolve_backoff_max_s: float = 5.0
    resolve_max_retries: int = 8
    # Deterministic fault injection (repro.ft.ChaosPlan) — tests/benches.
    chaos: ChaosPlan | None = None
    # Per-pool-clone straggler rotation (repro.ft.StragglerConfig): when
    # set, optimized executions are timed per clone and a persistently
    # slow clone is rotated out of round-robin.  Timing implies a device
    # sync per submit, so this is opt-in.
    straggler: StragglerConfig | None = None
    # Continuous batching (repro.serve.batching.BatchConfig): when set,
    # submit_async() routes through a bounded queue drained by one
    # background batcher thread that coalesces same-entry submits into
    # power-of-two buckets served by batched re-traces.  None keeps
    # submit_async() as a thin synchronous wrapper.
    batching: BatchConfig | None = None
    # Cost-model drift detection (repro.obs.drift): one in
    # ``drift.sample_every`` optimized requests is timed (device sync)
    # and folded into a per-entry EMA; when observed/predicted latency
    # leaves the threshold band the entry's plan is re-solved through
    # the background plan-refresh path.  None uses DriftConfig()
    # defaults; DriftConfig(enabled=False) turns it off.
    drift: DriftConfig | None = None


class Engine:
    def __init__(self, cfg: M.ModelConfig, params: Any,
                 sc: ServeConfig | None = None):
        self.cfg = cfg
        self.params = params
        self.sc = sc or ServeConfig()

        # named functions, not partials: the profiler trace and the HLO
        # name the programs jit_prefill and jit_decode_step
        def prefill(params, tokens, max_len):
            return M.prefill(params, cfg, tokens, max_len=max_len)

        def decode_step(params, cache, tokens):
            return M.decode_step(params, cfg, cache, tokens)

        self._prefill = jax.jit(prefill, static_argnames=("max_len",))
        # the cache is donated: the step updates it in place, and the
        # caller holds only the returned cache
        self._decode = jax.jit(decode_step, donate_argnames=("cache",))
        blocks = default_registry().counter(
            "repro_decode_kv_blocks_total",
            "KV cache blocks the decode steps read or skip", ("kind",))
        self._kv_read = blocks.labels("read")
        self._kv_skipped = blocks.labels("skipped")
        self._tr = _obs_tracer()
        self._gids = itertools.count()

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        if self.sc.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        probs = jax.nn.softmax(logits / self.sc.temperature, axis=-1)
        return jax.random.categorical(key, jnp.log(probs + 1e-9), axis=-1) \
            .astype(jnp.int32)

    def generate(self, prompts: np.ndarray, max_new_tokens: int, *,
                 return_logits: bool = False):
        """prompts (B, P) int32 -> (B, max_new_tokens) int32.

        With ``return_logits`` also returns the logits each token was
        sampled from, (B, max_new_tokens, V) float32: the prefill's for the
        first token, then one decode step's for each next one."""
        b, p = prompts.shape
        assert p + max_new_tokens <= self.sc.max_len, "exceeds max_len"
        span = self._tr.span
        gid = next(self._gids)
        key = jax.random.PRNGKey(self.sc.seed)
        with span("prefill", "generate", gid=gid, batch=b):
            logits, cache = self._prefill(
                params=self.params, tokens=jnp.asarray(prompts),
                max_len=self.sc.max_len)
            key, sub = jax.random.split(key)
            tok = self._sample(logits, sub)
        out = np.zeros((b, max_new_tokens), np.int32)
        seen: list[np.ndarray] = []
        done = np.zeros((b,), bool)
        steps = 0
        for t in range(max_new_tokens):
            with span("token", "decode", gid=gid, t=t, batch=b):
                with span("sync", "decode", gid=gid, t=t, batch=b):
                    if return_logits:
                        seen.append(np.asarray(logits))
                    host_tok = np.asarray(tok)
                out[:, t] = np.where(done, 0, host_tok)
                if self.sc.eos_id is not None:
                    done |= host_tok == self.sc.eos_id
                    if done.all():
                        break
                with span("dispatch", "decode", gid=gid, t=t, batch=b):
                    logits, cache = self._decode(params=self.params,
                                                 cache=cache, tokens=tok)
                    steps += 1
                with span("sample", "decode", gid=gid, t=t, batch=b):
                    key, sub = jax.random.split(key)
                    tok = self._sample(logits, sub)
        read, skipped = M.decode_kv_blocks(self.cfg, self.sc.max_len, p,
                                           steps)
        self._kv_read.inc(read)
        self._kv_skipped.inc(skipped)
        if return_logits:
            return out, np.stack(seen, axis=1)
        return out


def throughput_stats(n_tokens: int, seconds: float) -> dict:
    return {"tokens": n_tokens, "seconds": seconds,
            "tokens_per_s": n_tokens / max(seconds, 1e-9)}


def _rtol_for(dtype) -> float:
    """Canary tolerance per dtype (mirrors the frontend oracle bands)."""
    return 2e-2 if np.dtype(dtype).itemsize <= 2 else 2e-4


# Per-entry counter families: one definition each, labeled by entry name.
# The engine's MetricsRegistry owns them; _EntryHealth holds the labeled
# children so the hot path increments without any engine lock.
_ENTRY_COUNTERS = (
    ("ok", "repro_entry_ok_total", "optimized-path successes"),
    ("failures", "repro_entry_failures_total",
     "optimized-path failures (any site)"),
    ("fallbacks", "repro_entry_fallbacks_total",
     "requests served by the plain-jit path"),
    ("attempts", "repro_entry_attempts_total",
     "optimized-path tries (canary cadence)"),
    ("canaries", "repro_entry_canaries_total", "canary validations run"),
    ("canary_failures", "repro_entry_canary_failures_total",
     "canary validation mismatches"),
    ("deadline_misses", "repro_entry_deadline_misses_total",
     "admitted requests finished past budget"),
    ("resolve_attempts", "repro_entry_resolve_attempts_total",
     "background re-solve tries"),
    ("recovered", "repro_entry_recovered_total",
     "successful background recoveries"),
)


@dataclasses.dataclass
class _EntryHealth:
    """Per-entry resilience state: breaker, counters, recovery plumbing.

    Counter conservation contract (the accounting tests pin it down):
    ``ok + fallbacks == per_name[name]`` — every admitted request ends in
    exactly one bucket, whatever failed along the way.  The counters are
    labeled children of the engine's :class:`MetricsRegistry` families
    (``repro_entry_*_total{entry=...}``), so the same numbers back both
    ``stats()`` and the Prometheus exposition.
    """

    breaker: CircuitBreaker
    ok: Counter
    failures: Counter
    fallbacks: Counter
    attempts: Counter
    canaries: Counter
    canary_failures: Counter
    deadline_misses: Counter
    resolve_attempts: Counter
    recovered: Counter
    recovering: bool = False
    rotated: tuple[int, ...] = ()   # pool clones rotated out (straggler)
    straggler: StragglerMonitor | None = None
    last_error: str | None = None
    recovered_event: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    recovery_thread: threading.Thread | None = None

    def state(self, has_plan: bool) -> str:
        if not has_plan:
            return "fallback"       # registration-time failure: plain jit
        return {BreakerState.CLOSED: "ok",
                BreakerState.OPEN: "quarantined",
                BreakerState.HALF_OPEN: "half_open"}[self.breaker.state]

    def stats(self, has_plan: bool = True) -> dict:
        return {"state": self.state(has_plan),
                "ok": self.ok.value, "failures": self.failures.value,
                "fallbacks": self.fallbacks.value,
                "canaries": self.canaries.value,
                "canary_failures": self.canary_failures.value,
                "deadline_misses": self.deadline_misses.value,
                "resolve_attempts": self.resolve_attempts.value,
                "recovered": self.recovered.value,
                "recovering": self.recovering,
                "rotated_clones": list(self.rotated),
                "breaker": self.breaker.stats(),
                "last_error": self.last_error}


class PlanEngine:
    """Serve repeated plan executions off the compiled-program cache.

    Register (graph, plan) pairs under a model name, then submit input
    batches against them.  Requests resolve through the process-wide
    bounded LRU program cache (``repro.codegen.program_cache``): the
    (graph, plan, impl) fingerprint key is hashed once per registration,
    and every ``submit()`` is an O(1) keyed cache lookup — eviction-aware,
    so the cache's hit/eviction statistics stay the one source of truth.

    ``ServeConfig`` carries the serving knobs: program-cache bound,
    executable-pool size, the registration admission cap — and the
    resilience contract (deadlines, bounded in-flight depth,
    canary validation, circuit breakers, background re-solve, chaos
    injection; see the module docstring).

    Thread-safe: N server threads may ``submit`` (and register/unregister)
    against one engine concurrently — registry, key table and request
    counters mutate under an engine lock, the program cache under its own
    lock, and program execution itself runs outside both, so requests for
    warm programs never serialize on each other.
    """

    def __init__(self, impl: str | None = None,
                 sc: ServeConfig | None = None):
        from ..codegen import set_program_cache_size
        self._impl = impl
        self.sc = sc or ServeConfig()
        if self.sc.plan_store_dir:
            from ..store import set_default_dir
            set_default_dir(self.sc.plan_store_dir)
        if self.sc.program_cache_size is not None:
            set_program_cache_size(self.sc.program_cache_size)
        self._lock = threading.RLock()
        self._registry: dict[str, tuple[Any, Any]] = {}
        # (name, impl) -> program-cache key: fingerprints are hashed once
        # per registration, not per request — submit() is pure dispatch
        self._keys: dict[tuple[str, str], tuple] = {}
        self._last_use: dict[str, float] = {}
        # names registered through register_function: the TracedFunction
        # binds positional args to graph arrays and rebuilds result pytrees
        self._functions: dict[str, Any] = {}
        # -- observability -------------------------------------------------
        # One registry per engine: the single source of truth behind both
        # stats() and the Prometheus exposition (metrics.expose()).  The
        # legacy int attributes (requests, rejected, ...) are read-only
        # property shims over these counters.
        configure_logging()
        self.metrics = MetricsRegistry()
        m = self.metrics
        self._tr = _obs_tracer()
        self._c_requests = m.counter(
            "repro_requests_total", "admitted requests")
        self._c_per_name = m.counter(
            "repro_entry_requests_total", "admitted requests per entry",
            ("entry",))
        self._c_rejected = m.counter(
            "repro_rejected_total", "admission (overload) rejections")
        self._c_deadline_rejected = m.counter(
            "repro_deadline_rejected_total",
            "deadline expired before admission")
        self._c_deadline_misses = m.counter(
            "repro_deadline_misses_total",
            "admitted requests finished past budget")
        self._c_plan_refreshes = m.counter(
            "repro_plan_refreshes_total",
            "stale plans re-solved in background")
        self._c_buckets_presolved = m.counter(
            "repro_buckets_presolved_total",
            "batch buckets pre-solved at register time")
        self._c_drift_triggers = m.counter(
            "repro_drift_triggers_total",
            "cost-model drift events that triggered a plan refresh")
        self._c_program_builds = m.counter(
            "repro_program_builds_total",
            "program lookups that missed and built (compiled) on the "
            "request path")
        self._c_syncs = m.counter(
            "repro_request_syncs_total",
            "optimized runs synced with the device before returning",
            ("reason",))
        self._g_inflight = m.gauge(
            "repro_inflight", "requests currently admitted")
        self._h_latency = m.histogram(
            "repro_request_seconds", "submit wall time by serving path",
            ("path",))
        self._entry_families = {
            attr: m.counter(mname, help, ("entry",))
            for attr, mname, help in _ENTRY_COUNTERS
        }
        self._c_breaker_transitions = m.counter(
            "repro_breaker_transitions_total",
            "circuit-breaker state transitions", ("entry", "state"))
        m.register_invariant(
            "ok+fallbacks==requests per entry (at quiescence)",
            self._accounting_closed)
        self._drift = DriftDetector(self.sc.drift or DriftConfig(),
                                    clock=time.monotonic)
        # -- resilience state ---------------------------------------------
        self._health: dict[str, _EntryHealth] = {}
        # entries whose trace/solve failed at registration: served by the
        # plain-jit fallback alone until background re-solve succeeds
        self._fallback_only: dict[str, Any] = {}
        self._fallback_fns: dict[str, Any] = {}     # name -> jit(fn)
        self._reference_fns: dict[str, Any] = {}    # name -> ref executor
        # register_function provenance so background re-solve can retry
        # with the caller's solver budget/hardware
        self._reg_meta: dict[str, dict] = {}
        self._inflight_sem = (
            threading.BoundedSemaphore(self.sc.max_inflight)
            if self.sc.max_inflight else None)
        self._stop = threading.Event()
        # injectable time: breaker resets, timed optimized runs, and the
        # injected chaos delay (tests make both deterministic)
        self._clock = time.monotonic
        self._sleep = time.sleep
        self._rids = itertools.count()
        # background plan-refresh / bucket-presolve threads (stale store
        # hits, register-time bucket pre-solving) — joined in shutdown()
        self._bg_threads: list[threading.Thread] = []
        self._refreshing: set[str] = set()   # names with a refresh in flight
        # lazy: the batcher thread only starts on first submit_async()
        self._batcher: Batcher | None = None
        self._batcher_lock = threading.Lock()

    # -- legacy counter shims (registry-backed, read-only) -----------------
    @property
    def requests(self) -> int:
        return self._c_requests.value

    @property
    def per_name(self) -> dict[str, int]:
        return {k[0]: v for k, v in self._c_per_name.snapshot().items()}

    @property
    def rejected(self) -> int:
        return self._c_rejected.value

    @property
    def deadline_rejected(self) -> int:
        return self._c_deadline_rejected.value

    @property
    def deadline_misses(self) -> int:
        return self._c_deadline_misses.value

    @property
    def plan_refreshes(self) -> int:
        return self._c_plan_refreshes.value

    @property
    def buckets_presolved(self) -> int:
        return self._c_buckets_presolved.value

    def _accounting_closed(self) -> bool:
        """The per-entry conservation closure, asserted in one place:
        every admitted request ends in exactly one of ok/fallbacks.
        Holds at quiescence (no requests in flight)."""
        per_name = self.per_name
        with self._lock:
            health = dict(self._health)
        return all(
            h.ok.value + h.fallbacks.value == per_name.get(name, 0)
            for name, h in health.items())

    def check_invariants(self) -> list[str]:
        """Violated accounting invariants (empty when all closures hold).
        The batcher registers its closures in the same registry, so this
        covers both tiers.  Meaningful at quiescence; in-flight requests
        legitimately sit between the 'admitted' and 'resolved' counters."""
        return self.metrics.check_invariants()

    def note_predicted_latency(self, name: str, latency_s: float) -> None:
        """Seed/override the drift detector's predicted latency for an
        entry (benches use this to simulate a miscalibrated cost model)."""
        self._drift.note_predicted(name, latency_s)

    # -- registration -----------------------------------------------------
    def register(self, name: str, graph, plan) -> None:
        """Admit a (graph, plan) pair; past ``sc.max_plans`` registrations
        the least-recently-submitted name is evicted first."""
        with self._lock:
            if self.sc.max_plans is not None and name not in self._registry:
                while len(self._registry) >= max(1, self.sc.max_plans):
                    lru = min(self._registry,
                              key=lambda n: self._last_use.get(n, 0.0))
                    self.unregister(lru)
            self._registry[name] = (graph, plan)
            self._last_use[name] = time.monotonic()
            self._functions.pop(name, None)   # plain graphs shed any old
            self._keys = {k: v for k, v in self._keys.items()  # traced glue
                          if k[0] != name}
            self._health.pop(name, None)      # fresh entry, fresh health
            for fam in self._entry_families.values():
                fam.remove(name)              # ... fresh labeled counters
            for st in BreakerState:
                self._c_breaker_transitions.remove(name, st.value)
            self._fallback_only.pop(name, None)
            self._fallback_fns.pop(name, None)
            self._reference_fns.pop(name, None)
        # fresh plan, fresh drift baseline (resets the observed EMA)
        predicted = getattr(plan, "latency_s", 0.0) if plan is not None else 0.0
        if predicted > 0.0:
            self._drift.note_predicted(name, predicted)
        else:
            self._drift.forget(name)

    def register_function(self, name: str, fn, example_inputs,
                          *, solver_opts=None, hw=None):
        """Trace an arbitrary JAX callable (``repro.frontend``), solve its
        graph and register it for serving under ``name``.

        ``example_inputs`` is the positional-argument tuple fixing shapes
        and dtypes.  Requests for function entries pass the same tuple
        shape to :meth:`submit` (or a dict of graph arrays, as for plain
        registrations).  Returns the :class:`TracedFunction` so callers can
        inspect coverage or validate against the ``jax.jit`` oracle.

        With ``sc.fallback`` (the default), a trace/solve failure does NOT
        raise: the entry is registered in degraded mode — every submit is
        served by plain ``jax.jit(fn)`` — quarantined in :meth:`stats`,
        and re-traced/re-solved in the background with exponential
        backoff.  Returns ``None`` in that case.
        """
        from ..frontend import trace
        try:
            tf = trace(fn, *example_inputs, name=name)
            if not tf.graph.statements:
                raise ValueError(
                    f"{name}: function lowered to an empty graph (pure "
                    "passthrough) — nothing to serve")
            # allow_stale: with a plan store configured, a plan priced for
            # an older hardware profile is accepted here (cold solve off
            # the registration path) and re-solved in the background below
            plan = tf.solve(hw=hw, opts=solver_opts, allow_stale=True)
        except Exception as exc:
            if not self.sc.fallback:
                raise
            log.warning("%s: trace/solve failed (%s); registering the "
                        "plain-jit fallback and re-solving in background",
                        name, exc)
            with self._lock:
                self.register(name, None, None)
                self._fallback_only[name] = jax.jit(fn)
                self._reg_meta[name] = {
                    "fn": fn, "example_inputs": tuple(example_inputs),
                    "solver_opts": solver_opts, "hw": hw}
                health = self._health_for(name)
                health.last_error = f"{type(exc).__name__}: {exc}"
            health.breaker.force_open()
            self._start_recovery(name, self._current_impl())
            return None
        with self._lock:
            # registry entry + function-binding glue must appear atomically:
            # a concurrent positional-tuple submit between the two would see
            # the entry without the binder and hand the raw tuple to the
            # program (the lock is reentrant, register() retakes it)
            self.register(name, tf.graph, plan)
            self._functions[name] = tf
            self._reg_meta[name] = {
                "fn": fn, "example_inputs": tuple(example_inputs),
                "solver_opts": solver_opts, "hw": hw}
        if plan is not None and getattr(plan, "stale_hw", False):
            # serve the drifted plan now; re-solve + store update happen
            # off the request path
            self._start_plan_refresh(name)
        bc = self.sc.batching
        if bc is not None and bc.presolve and BATCH_SEP not in name:
            self._start_bucket_presolve(name)
        return tf

    def _start_plan_refresh(self, name: str) -> None:
        """Background re-solve for a stale-hardware store hit: solve fresh
        (bypassing the store read, updating the store write), recompile,
        revalidate, and atomically swap the entry — requests keep being
        served by the stale plan until the fresh one is proven."""
        impl = self._current_impl()
        with self._lock:
            if name in self._refreshing:
                return                  # one refresh in flight per entry
            self._refreshing.add(name)

        def _loop():
            from ..ft.serve import BackoffPolicy
            policy = BackoffPolicy(
                base_s=self.sc.resolve_backoff_s,
                mult=self.sc.resolve_backoff_mult,
                max_s=self.sc.resolve_backoff_max_s,
                retries=self.sc.resolve_max_retries)
            with self._tr.span("refresh", "plan", entry=name):
                try:
                    for attempt, delay in enumerate(policy.delays(), start=1):
                        if self._stop.wait(delay):
                            return
                        with self._lock:
                            if name not in self._registry:
                                return  # unregistered while refreshing
                        try:
                            chaos = self.sc.chaos
                            if chaos is not None:
                                chaos.on_refresh(name)
                            self._rebuild(name, impl)
                        except Exception as exc:
                            log.info(
                                "plan-refresh entry=%s attempt=%d "
                                "backoff_s=%.3f failed: %s",
                                name, attempt, delay, exc)
                            continue
                        self._c_plan_refreshes.inc()
                        log.info(
                            "plan-refresh entry=%s attempt=%d succeeded: "
                            "stale plan refreshed in background",
                            name, attempt)
                        return
                    log.warning("plan-refresh entry=%s gave up after %d "
                                "attempts", name, self.sc.resolve_max_retries)
                finally:
                    with self._lock:
                        self._refreshing.discard(name)

        t = threading.Thread(target=_loop, daemon=True,
                             name=f"repro-plan-refresh-{name}")
        with self._lock:
            self._bg_threads.append(t)
        t.start()

    def _start_bucket_presolve(self, name: str) -> None:
        """Pre-solve the continuous-batching bucket ladder for ``name`` at
        registration time, so the first coalesced flush pays no trace or
        solve (with a warm plan store it pays neither even cold)."""

        def _loop():
            try:
                with self._tr.span("presolve", "plan", entry=name):
                    n = self.batcher().presolve(name, stop=self._stop)
            except Exception as exc:
                log.info("bucket-presolve entry=%s failed: %s", name, exc)
                return
            self._c_buckets_presolved.inc(n)
            log.info("bucket-presolve entry=%s buckets=%d done", name, n)

        t = threading.Thread(target=_loop, daemon=True,
                             name=f"repro-presolve-{name}")
        with self._lock:
            self._bg_threads.append(t)
        t.start()

    def unregister(self, name: str) -> None:
        self._c_per_name.remove(name)
        for fam in self._entry_families.values():
            fam.remove(name)
        for st in BreakerState:
            self._c_breaker_transitions.remove(name, st.value)
        self._drift.forget(name)
        with self._lock:
            self._registry.pop(name, None)
            self._last_use.pop(name, None)
            self._functions.pop(name, None)
            self._keys = {k: v for k, v in self._keys.items()
                          if k[0] != name}
            self._health.pop(name, None)
            self._fallback_only.pop(name, None)
            self._fallback_fns.pop(name, None)
            self._reference_fns.pop(name, None)
            self._reg_meta.pop(name, None)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._registry)

    def shutdown(self, timeout: float = 60.0) -> None:
        """Stop background recovery threads and wait for any in-flight
        re-solve to finish (an attempt mid-solve cannot be interrupted,
        only not-followed-by-another).  Daemon threads also die with the
        process — this is for tests and orderly replica teardown, so a
        stopped engine leaves the process-wide program cache alone.  The
        batching tier (if started) drains its queue first: no enqueued
        future is abandoned."""
        with self._batcher_lock:
            batcher = self._batcher
        if batcher is not None:
            batcher.shutdown(timeout)
        self._stop.set()
        with self._lock:
            threads = [h.recovery_thread for h in self._health.values()
                       if h.recovery_thread is not None]
            threads += self._bg_threads
        for t in threads:
            t.join(timeout)

    # -- warmup -----------------------------------------------------------
    def warmup(self, name: str, inputs: dict) -> float:
        """Compile-and-first-run; returns seconds spent (the cold cost the
        cache amortizes away for every later request).

        Warms **every** pool clone, not just clone 0 — otherwise the first
        ``pool_size - 1`` concurrent requests after warmup each pay a
        first-call trace on a cold clone.  Every warmup execution flows
        through :meth:`submit`, so per-entry hit counters, LRU recency and
        ``per_name`` accounting all see the warmup (a just-warmed plan is
        MRU, never the next eviction victim).  With a persistent
        compilation cache configured, a replica warming a program another
        replica already compiled deserializes the artifact instead of
        re-lowering — the warm-start path."""
        from ..codegen import program_cache
        t0 = time.monotonic()
        out = self.submit(name, inputs)
        for v in jax.tree_util.tree_leaves(out):
            v.block_until_ready()
        impl = self._current_impl()
        if self.sc.pool_size is not None:
            # the engine's own pool contract — valid even if the entry was
            # already evicted again by a concurrent replica
            clones = self.sc.pool_size
        else:
            with self._lock:
                key = self._keys.get((name, impl))
            entry = program_cache().entry(key) if key is not None else None
            clones = entry.program.pool_size if entry is not None else 1
        for _ in range(clones - 1):
            out = self.submit(name, inputs)
            for v in jax.tree_util.tree_leaves(out):
                v.block_until_ready()
        return time.monotonic() - t0

    # -- request path -----------------------------------------------------
    def _current_impl(self) -> str:
        from ..kernels import dispatch
        return self._impl or dispatch.current_impl()

    def _health_for(self, name: str) -> _EntryHealth:
        with self._lock:
            health = self._health.get(name)
            if health is None:
                trans = self._c_breaker_transitions
                health = self._health[name] = _EntryHealth(
                    breaker=CircuitBreaker(
                        self.sc.breaker_threshold,
                        self.sc.breaker_reset_s, clock=self._clock,
                        on_transition=lambda state, _n=name:
                            trans.labels(_n, state).inc()),
                    **{attr: fam.labels(name)
                       for attr, fam in self._entry_families.items()})
            return health

    def _resolve(self, name: str, impl: str, rid: int):
        from ..codegen import compiled_program, program_cache, program_key
        with self._tr.span("resolve", "request", entry=name, rid=rid) as sp:
            with self._lock:
                key = self._keys.get((name, impl))
                if key is None:
                    graph, plan = self._registry[name]
                    key = program_key(graph, plan, impl)
                    self._keys[(name, impl)] = key
                else:
                    graph, plan = self._registry[name]
            # fast path: an O(1) keyed hit honouring this engine's pool
            # contract (a pool-mismatched entry is NOT counted as a hit —
            # compiled_program rebuilds and re-admits it below)
            prog = program_cache().get_if(key, self.sc.pool_size)
            sp.set(miss=prog is None)
            if prog is not None:
                return prog
            # miss or evicted or foreign pool: build once (per-key build
            # lock inside compiled_program), re-admitted as MRU
            self._c_program_builds.inc()
            return compiled_program(graph, plan, impl,
                                    pool_size=self.sc.pool_size)

    def batcher(self) -> Batcher:
        """The engine's continuous-batching front door (lazily started on
        first use).  Requires ``sc.batching``; raises otherwise."""
        if self.sc.batching is None:
            raise RuntimeError(
                "continuous batching is not configured — set "
                "ServeConfig.batching = BatchConfig(...)")
        with self._batcher_lock:
            if self._batcher is None:
                self._batcher = Batcher(self, self.sc.batching)
            return self._batcher

    def submit_async(self, name: str, inputs, *,
                     deadline_s: float | None = None):
        """Asynchronous submit: returns a ``concurrent.futures.Future``
        resolving to the same value :meth:`submit` would return.

        With ``sc.batching`` configured the request enters the bounded
        batching queue, where same-entry submits are coalesced into one
        batched program execution (see :mod:`repro.serve.batching`);
        admission rejections (``EngineOverloaded``) and caller contract
        errors still raise synchronously, while execution-time failures
        (including ``DeadlineExceeded``) resolve the future.  Without
        batching this is a thin synchronous wrapper — the request runs
        inline and the returned future is already done — so callers can
        target either engine flavor uniformly.
        """
        if self.sc.batching is not None:
            return self.batcher().submit(name, inputs,
                                         deadline_s=deadline_s)
        from concurrent.futures import Future
        fut: Future = Future()
        try:
            fut.set_result(self.submit(name, inputs,
                                       deadline_s=deadline_s))
        except Exception as exc:
            fut.set_exception(exc)
        return fut

    def submit(self, name: str, inputs, *,
               deadline_s: float | None = None, _info: dict | None = None) \
            -> Any:
        """Execute one request; hits the compiled program for ``name``.

        ``inputs`` is a dict of graph arrays for plain registrations.  For
        ``register_function`` entries it may also be a tuple/list of
        positional arguments matching the traced signature — the request is
        bound through the TracedFunction and returns the function's result
        pytree instead of a raw array dict.

        ``deadline_s`` overrides ``sc.deadline_s`` for this request.
        Raises :class:`~repro.ft.EngineOverloaded` when the bounded
        in-flight depth stays full past the admission timeout, and
        :class:`~repro.ft.DeadlineExceeded` when the budget expires before
        admission; any post-admission failure degrades to the plain-jit
        fallback (``sc.fallback``) instead of raising.

        ``_info`` (internal, used by the batching tier's accounting) is
        annotated with ``{"path": "optimized" | "fallback"}`` for the path
        that served the request.
        """
        t0 = time.monotonic()
        rid = next(self._rids)
        with self._tr.span("submit", "request", entry=name, rid=rid):
            deadline = deadline_s if deadline_s is not None \
                else self.sc.deadline_s
            sem = self._inflight_sem
            if sem is not None:
                timeout = self.sc.admission_timeout_s
                if deadline is not None:
                    timeout = min(timeout, deadline)
                with self._tr.span("admission", "request", entry=name,
                                   rid=rid):
                    admitted = sem.acquire(timeout=max(0.0, timeout))
                if not admitted:
                    if deadline is not None \
                            and time.monotonic() - t0 >= deadline:
                        self._c_deadline_rejected.inc()
                        raise DeadlineExceeded(
                            f"{name}: deadline {deadline:.3f}s expired "
                            "before admission (engine at max_inflight="
                            f"{self.sc.max_inflight})")
                    self._c_rejected.inc()
                    raise EngineOverloaded(
                        f"{name}: {self.sc.max_inflight} requests in "
                        f"flight; none drained within {timeout:.3f}s")
            try:
                self._g_inflight.inc()
                return self._submit_admitted(name, inputs, t0, deadline,
                                             rid, _info)
            finally:
                self._g_inflight.dec()
                if sem is not None:
                    sem.release()

    def _submit_admitted(self, name: str, inputs, t0: float,
                         deadline: float | None, rid: int,
                         _info: dict | None = None) -> Any:
        impl = self._current_impl()
        with self._lock:
            if name not in self._registry:
                raise KeyError(name)
            tf = self._functions.get(name)
            has_plan = self._registry[name][1] is not None
        health = self._health_for(name)
        env = None
        if tf is not None and not isinstance(inputs, dict):
            # argument-contract errors (bad pytree/shape/dtype) are caller
            # bugs: they raise before the request is counted and never
            # touch the breaker
            env = tf.bind_args(tuple(inputs))
        self._c_requests.inc()
        self._c_per_name.labels(name).inc()
        with self._lock:
            self._last_use[name] = time.monotonic()
        if has_plan and health.breaker.allow():
            try:
                out = self._run_optimized(
                    name, impl, tf, env if env is not None else inputs,
                    health, rid)
            except Exception as exc:
                self._note_failure(name, impl, health, exc)
                if not self.sc.fallback:
                    raise
            else:
                health.ok.inc()
                health.breaker.record_success()
                self._note_deadline(t0, deadline, health)
                self._h_latency.labels("optimized").observe(
                    time.monotonic() - t0)
                if _info is not None:
                    _info["path"] = "optimized"
                if env is not None:
                    return tf.unbind(out, env)
                return out
        with self._tr.span("fallback", "request", entry=name, rid=rid):
            out = self._run_fallback(name, tf, env, inputs, health)
        self._note_deadline(t0, deadline, health)
        self._h_latency.labels("fallback").observe(time.monotonic() - t0)
        if _info is not None:
            _info["path"] = "fallback"
        return out

    def _run_optimized(self, name: str, impl: str, tf, env: dict,
                       health: _EntryHealth, rid: int) -> dict:
        """The one-dispatch path; raises on any failure (compile, execute,
        injected chaos, NaN guard, canary mismatch)."""
        chaos = self.sc.chaos
        if chaos is not None:
            chaos.on_compile(name)
        prog = self._resolve(name, impl, rid)
        if chaos is not None:
            chaos.on_execute(name)
        attempt = health.attempts.inc() - 1
        canary = self.sc.canary_every > 0 \
            and attempt % self.sc.canary_every == 0
        # one in drift.sample_every optimized runs is timed (device sync)
        # to feed the cost-model drift EMA; sampling keeps the sync off
        # the steady-state path
        drift_sample = self._drift.config.enabled \
            and self._drift.should_sample(name)
        straggler = self.sc.straggler is not None and prog.pool_size > 1
        nan_always = self.sc.nan_guard == "always"
        t_run = self._clock()
        with self._tr.span("execute", "request", entry=name,
                           rid=rid) as sp:
            out, clone = prog.run(env)
            if chaos is not None:
                delay = chaos.execute_delay(name, clone)
                if delay > 0.0:
                    self._sleep(delay)
                out = chaos.corrupt_outputs(name, out)
            sp.set(clone=clone)
        # why this run waits for the device, if it does
        reason = ("drift" if drift_sample else "canary" if canary
                  else "straggler" if straggler
                  else "nan_guard" if nan_always else None)
        if reason is not None:
            self._c_syncs.labels(reason).inc()
            with self._tr.span("sync", "request", entry=name, rid=rid,
                               reason=reason):
                jax.block_until_ready(list(out.values()))
        elapsed = self._clock() - t_run
        if drift_sample:
            self._note_drift(name, elapsed)
        if straggler:
            self._observe_clone(name, health, prog, clone, elapsed)
        guard_nan = nan_always or (canary and self.sc.nan_guard == "canary")
        if canary:
            health.canaries.inc()
        if guard_nan:
            self._guard_finite(name, out)
        if canary:
            with self._tr.span("canary", "request", entry=name, rid=rid):
                self._validate_canary(name, tf, env, out, health)
        return out

    def _note_drift(self, name: str, elapsed: float) -> None:
        """Fold one observed optimized-path latency into the drift EMA;
        a threshold crossing re-prices the plan through the background
        refresh path (the cost model drifted from reality)."""
        ev = self._drift.observe(name, elapsed)
        if ev is None:
            return
        self._c_drift_triggers.inc()
        log.warning(
            "drift entry=%s predicted_s=%.3g observed_ema_s=%.3g "
            "ratio=%.2f samples=%d — re-solving in background",
            ev.name, ev.predicted_s, ev.observed_ema_s, ev.ratio,
            ev.samples)
        self._start_plan_refresh(name)

    def _guard_finite(self, name: str, out: dict) -> None:
        for k, v in out.items():
            if jnp.issubdtype(v.dtype, jnp.floating) \
                    and not bool(jnp.all(jnp.isfinite(v))):
                raise MiscompileError(
                    f"{name}: output {k!r} contains NaN/inf — optimized "
                    "path quarantined")

    def _validate_canary(self, name: str, tf, env: dict, out: dict,
                         health: _EntryHealth) -> None:
        """Compare the optimized outputs against the plain-jit oracle;
        a mismatch is a miscompile (wrong kernel output) — the entry is
        quarantined and this request re-served by the oracle path."""
        from ..codegen import allclose
        try:
            if tf is not None:
                got = tf.unbind(out, env)
                flat = [env[n] for n in tf.record.in_names]
                args = jax.tree_util.tree_unflatten(tf.in_tree, list(flat))
                expect = self._fallback_fn(name, tf)(*args)
                g_flat = jax.tree_util.tree_leaves(got)
                e_flat = jax.tree_util.tree_leaves(expect)
                bad = len(g_flat) != len(e_flat) or any(
                    not allclose(g, e, rtol=_rtol_for(e.dtype))
                    for g, e in zip(g_flat, e_flat))
            else:
                expect = self._reference_fn(name)(env)
                bad = any(not allclose(out[k], expect[k],
                                       rtol=_rtol_for(expect[k].dtype))
                          for k in expect)
        except MiscompileError:
            raise
        except Exception as exc:
            # the oracle itself failing is an engine problem, not proof of
            # a miscompile; treat as an optimized-path failure all the same
            raise MiscompileError(
                f"{name}: canary oracle execution failed: {exc}") from exc
        if bad:
            health.canary_failures.inc()
            raise MiscompileError(
                f"{name}: canary validation mismatch vs the plain-jit "
                "oracle — corrupted kernel output")

    def _fallback_fn(self, name: str, tf):
        with self._lock:
            fn = self._fallback_fns.get(name)
            if fn is None:
                fn = self._fallback_fns[name] = jax.jit(tf.fn)
            return fn

    def _reference_fn(self, name: str):
        from ..codegen import reference_executor
        with self._lock:
            fn = self._reference_fns.get(name)
            if fn is None:
                graph, _ = self._registry[name]
                fn = self._reference_fns[name] = reference_executor(graph)
            return fn

    def _run_fallback(self, name: str, tf, env, inputs,
                      health: _EntryHealth) -> Any:
        """Serve the request on the plain-jit path (guaranteed-correct
        baseline): ``jax.jit(fn)`` for function entries, the statement
        reference oracle for graph registrations."""
        health.fallbacks.inc()
        with self._lock:
            fb = self._fallback_only.get(name)
        if fb is not None:
            return fb(*tuple(inputs))
        if tf is not None:
            fn = self._fallback_fn(name, tf)
            if env is not None:
                return fn(*tuple(inputs))
            flat = [inputs[n] for n in tf.record.in_names]
            args = jax.tree_util.tree_unflatten(tf.in_tree, list(flat))
            return fn(*args)
        return self._reference_fn(name)(inputs)

    def _note_deadline(self, t0: float, deadline: float | None,
                       health: _EntryHealth) -> None:
        if deadline is not None and time.monotonic() - t0 > deadline:
            self._c_deadline_misses.inc()
            health.deadline_misses.inc()

    def _observe_clone(self, name: str, health: _EntryHealth, prog,
                       clone: int, elapsed: float) -> None:
        with self._lock:
            mon = health.straggler
            if mon is None or mon.n_hosts != prog.pool_size:
                mon = health.straggler = StragglerMonitor(
                    prog.pool_size, self.sc.straggler)
            flagged = mon.observe_one(clone, elapsed)
            if flagged and clone not in mon.reassigned:
                if prog.disable_clone(clone):
                    mon.demote(clone)
                    health.rotated = tuple(
                        sorted(set(health.rotated) | {clone}))
                    log.warning(
                        "%s: pool clone %d persistently slow "
                        "(%.1fms) — rotated out of round-robin",
                        name, clone, elapsed * 1e3)

    # -- quarantine + background re-solve ---------------------------------
    def _note_failure(self, name: str, impl: str, health: _EntryHealth,
                      exc: Exception) -> None:
        health.failures.inc()
        with self._lock:
            health.last_error = f"{type(exc).__name__}: {exc}"
        if isinstance(exc, MiscompileError):
            # wrong values are never a transient: quarantine immediately
            health.breaker.force_open()
            opened = True
        else:
            opened = health.breaker.record_failure()
        if health.breaker.state is BreakerState.OPEN:
            # the quarantined program must not be served again on recovery:
            # drop it from the process-wide cache so re-solve starts clean
            from ..codegen import program_cache
            with self._lock:
                key = self._keys.pop((name, impl), None)
            if key is not None:
                program_cache().invalidate(key)
        if opened:
            log.warning("%s: optimized path quarantined (%s); serving "
                        "plain-jit fallback, re-solving in background",
                        name, health.last_error)
            self._start_recovery(name, impl)

    def _start_recovery(self, name: str, impl: str) -> None:
        health = self._health_for(name)
        with self._lock:
            if health.recovering or self._stop.is_set():
                return
            health.recovering = True
            health.recovered_event.clear()
        t = threading.Thread(target=self._recovery_loop, args=(name, impl),
                             daemon=True, name=f"repro-resolve-{name}")
        with self._lock:
            health.recovery_thread = t
        t.start()

    def _recovery_loop(self, name: str, impl: str) -> None:
        from ..ft.serve import BackoffPolicy
        health = self._health_for(name)
        policy = BackoffPolicy(
            base_s=self.sc.resolve_backoff_s,
            mult=self.sc.resolve_backoff_mult,
            max_s=self.sc.resolve_backoff_max_s,
            retries=self.sc.resolve_max_retries)
        for attempt, delay in enumerate(policy.delays(), start=1):
            if self._stop.wait(delay):
                break
            with self._lock:
                if name not in self._registry:
                    break               # unregistered while quarantined
            health.resolve_attempts.inc()
            try:
                self._rebuild(name, impl)
            except Exception as exc:
                with self._lock:
                    health.last_error = f"{type(exc).__name__}: {exc}"
                log.info(
                    "re-solve entry=%s attempt=%d backoff_s=%.3f "
                    "failed: %s", name, attempt, delay, exc)
                continue
            health.breaker.record_success()     # closes: next submit is
            health.recovered.inc()              # optimized again
            with self._lock:
                health.recovering = False
            health.recovered_event.set()
            log.info("re-solve entry=%s attempt=%d succeeded; breaker "
                     "closed", name, attempt)
            return
        else:
            log.warning("re-solve entry=%s gave up after %d attempts; "
                        "entry stays on the fallback path",
                        name, self.sc.resolve_max_retries)
        with self._lock:
            health.recovering = False

    def _rebuild(self, name: str, impl: str) -> None:
        """One recovery attempt: re-trace/re-solve as needed, compile the
        program eagerly, and validate it against the plain-jit oracle on
        probe inputs before the breaker may close."""
        from ..codegen import (allclose, compiled_program, program_key,
                               random_inputs, reference_executor)
        with self._lock:
            meta = self._reg_meta.get(name)
            graph, plan = self._registry.get(name, (None, None))
            tf = self._functions.get(name)
            fallback_only = name in self._fallback_only
        if fallback_only or (tf is None and graph is None):
            # registration never succeeded: retry the full trace + solve
            from ..frontend import trace
            tf = trace(meta["fn"], *meta["example_inputs"], name=name)
            if not tf.graph.statements:
                raise ValueError(f"{name}: still lowers to an empty graph")
            plan = tf.solve(hw=meta["hw"], opts=meta["solver_opts"])
            graph = tf.graph
        elif tf is not None:
            # quarantined traced entry: re-solve fresh (calibration may
            # have drifted; the old plan produced the failure).  refresh
            # bypasses the plan-store read — a stored plan is exactly what
            # must not be trusted here — but still writes the result back,
            # so the store converges to the re-solved plan for every
            # replica
            from ..core.solver import SolverOptions, solve
            opts = (meta or {}).get("solver_opts") \
                or SolverOptions(time_budget_s=20.0)
            plan = solve(graph, (meta or {}).get("hw"), opts,
                         refresh=True)
        # graph-only entries keep their externally supplied plan: the
        # rebuild recompiles and revalidates the program
        prog = compiled_program(graph, plan, impl,
                                pool_size=self.sc.pool_size)
        if tf is not None:
            env = tf.bind(list(tf.example_flat))
            out = prog(env)
            got = jax.tree_util.tree_leaves(tf.unbind(out, env))
            args = jax.tree_util.tree_unflatten(tf.in_tree,
                                                list(tf.example_flat))
            expect = jax.tree_util.tree_leaves(jax.jit(tf.fn)(*args))
            if len(got) != len(expect) or any(
                    not allclose(g, e, rtol=_rtol_for(e.dtype))
                    for g, e in zip(got, expect)):
                raise MiscompileError(
                    f"{name}: rebuilt program still fails oracle "
                    "validation")
        else:
            env = random_inputs(graph, seed=0)
            out = prog(env)
            expect = reference_executor(graph)(env)
            if any(not allclose(out[k], expect[k]) for k in expect):
                raise MiscompileError(
                    f"{name}: rebuilt program still fails oracle "
                    "validation")
        with self._lock:
            self._registry[name] = (graph, plan)
            self._keys = {k: v for k, v in self._keys.items()
                          if k[0] != name}
            self._keys[(name, impl)] = program_key(graph, plan, impl)
            if tf is not None:
                self._functions[name] = tf
                self._fallback_only.pop(name, None)
            self._reference_fns.pop(name, None)
        # the re-solved plan is the new drift baseline (EMA resets)
        predicted = getattr(plan, "latency_s", 0.0) if plan is not None else 0.0
        if predicted > 0.0:
            self._drift.note_predicted(name, predicted)

    # -- statistics -------------------------------------------------------
    def stats(self) -> dict:
        """Serving statistics: engine request counts, the global program
        cache (size/capacity, hits/misses/evictions, per-entry detail),
        per-pool occupancy of every program this engine serves, the
        frontend trace cache (hits, size, per-entry coverage) feeding
        ``register_function`` entries, the ``resilience`` block —
        admission rejections, deadline accounting, and per-entry health
        (breaker state, fallbacks, canary results, recovery progress) —
        and the ``drift`` block (cost-model predicted vs. observed
        latency per entry).

        Lock discipline: the metrics-registry and drift snapshots come
        first (their own locks only), then the engine lock covers a
        plain-data copy; every sub-object that takes its own lock
        (breakers, batcher, program cache, trace cache) is consulted
        with NO engine lock held — ``stats()`` can never deadlock
        against a concurrent ``submit`` storm."""
        from ..codegen import cache_stats, persistent_cache_dir, program_cache
        from ..frontend import trace_cache_stats
        cache = program_cache()
        # 1) registry-backed counters + drift: no engine lock, no nesting
        requests = self._c_requests.value
        per_name = self.per_name
        drift = self._drift.stats()
        plan_store = {
            "dir": self.sc.plan_store_dir,
            "refreshes": self._c_plan_refreshes.value,
            "buckets_presolved": self._c_buckets_presolved.value,
        }
        # 2) engine lock: copy plain data only — no sub-object calls
        with self._lock:
            keys = dict(self._keys)
            registered = len(self._registry)
            functions = sorted(self._functions)
            health_refs = {
                name: (h, self._registry.get(name, (None, None))[1]
                       is not None)
                for name, h in self._health.items()}
        # 3) sub-objects with their own locks, engine lock released
        health = {name: h.stats(has_plan)
                  for name, (h, has_plan) in health_refs.items()}
        resilience = {
            "rejected": self._c_rejected.value,
            "deadline_rejected": self._c_deadline_rejected.value,
            "deadline_misses": self._c_deadline_misses.value,
            "inflight": self._g_inflight.value,
            "max_inflight": self.sc.max_inflight,
            "entries": health,
        }
        pools = {}
        for (name, impl), key in keys.items():
            entry = cache.entry(key)
            if entry is not None:
                p = entry.program
                pools[f"{name}/{impl}"] = {
                    "pool_size": p.pool_size,
                    "next": p.calls % p.pool_size,
                    "calls": p.calls,
                    "n_segments": p.n_segments,
                    "disabled_clones": list(p.disabled_clones),
                }
        with self._batcher_lock:
            batcher = self._batcher
        batching = batcher.stats() if batcher is not None else None
        s = cache_stats(detail=True)
        hit_rate = s["hits"] / max(1, s["hits"] + s["misses"])
        return {"requests": requests,
                "batching": batching,
                "registered": registered,
                "functions": functions,
                "per_name": per_name,
                "hit_rate": round(hit_rate, 4),
                "pools": pools,
                "persistent_cache_dir": persistent_cache_dir(),
                "trace_cache": trace_cache_stats(),
                "plan_store": plan_store,
                "resilience": resilience,
                "drift": drift,
                **s}
