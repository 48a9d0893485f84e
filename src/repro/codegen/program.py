"""Whole-plan compiled programs: the fused DAG as few ``jax.jit`` segments.

PR 1's executor walked the DAG in a Python loop — one ``jax.jit`` call per
task — so independent tasks serialized on the host dispatch path and every
inter-task edge round-tripped through HBM.  PR 2 lowered the *whole*
dataflow program into a single jitted callable.  This module is the serving
generation of that engine, with three production mechanisms on top:

* **materialization segments** — XLA CPU's fusion pass *clones* a cheap-to-
  recompute producer into every consumer fusion, even through
  ``optimization_barrier`` and even when the producer is a program output
  (measured on gemver: the rank-2 update ran once per consumer dot, turning
  the fusion win into a 0.55x loss).  The program is therefore split at
  multi-consumer producer boundaries: each segment is its own executable, so
  the producer's buffer is materialized exactly once and duplication is
  structurally impossible.  Graphs without multi-consumer intermediates
  (most of PolyBench) keep the original single-program lowering.
* **executable pool** — each program optionally holds ``pool_size`` cloned
  sets of its segment executables, served round-robin, so concurrent callers
  (or cross-call pipelining on memory-bound graphs) never contend on one
  executable instance.  ``REPRO_PROGRAM_POOL_SIZE`` sets the default.
* **bounded LRU program cache** — programs are cached process-wide, keyed by
  (graph fingerprint, plan fingerprint, impl), with per-entry hit/last-use/
  size stats and LRU eviction at ``REPRO_PROGRAM_CACHE_SIZE`` entries, so a
  replica serving many distinct plans has a bounded footprint.  Cache and
  pool are thread-safe: concurrent servers hit under the cache lock,
  misses for the same key compile once behind a per-key build lock, and
  the round-robin cursor never hands two callers the same clone index.
  JAX's persistent compilation cache (:func:`enable_compile_cache`, at
  the directory :func:`compile_cache_dir` resolves) lets processes share
  compiled artifacts: a warm process's first compile of a known program
  deserializes instead of re-lowering.

The input shapes/dtypes dimension of the cache key is carried by
``jax.jit``'s own aval cache underneath, so a repeated call with identical
shapes re-traces nothing — that is what makes the serving path
(`repro.serve.PlanEngine`) zero-overhead after the first request.

Graphs need not come from the polybench builders: the frontend
(`repro.frontend`) lowers traced jaxprs into graphs whose unsupported
regions are **opaque passthrough segments** — statements whose bodies are
registered residual callables (``repro.codegen.reference.register_opaque``)
evaluated inline while the segment executable traces.  They inline into the
same per-segment ``jax.jit`` programs as contraction kernels (XLA CSE
collapses a multi-output segment's repeated prefix into one computation),
participate in wave scheduling and multi-consumer materialization splits,
and cost nothing at execution time beyond the residual computation itself.
``unit_kinds()`` reports how much of a program is plan-tiled contraction
versus einsum/opaque fallback.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import OrderedDict
from typing import Callable

import jax

from ..core.fusion import FusedGraph, fuse
from ..core.plan import ExecutionPlan
from ..core.taskgraph import TaskGraph
from .lower import TaskLowering, lower_task
from .schedule import WaveSchedule, wave_schedule

#: Default LRU capacity of the process-wide program cache.
DEFAULT_CACHE_SIZE = 64
#: Default executable-pool size per cache entry (1 = no cloning).
DEFAULT_POOL_SIZE = 1


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, default)))
    except ValueError:
        return default


# ---------------------------------------------------------------------------
# Fingerprints (cache keys) — canonical definitions live in
# core/fingerprint.py (import-light, shared with the plan store); these
# re-exports keep the historical import site working.
# ---------------------------------------------------------------------------
from ..core.fingerprint import graph_fingerprint, plan_fingerprint  # noqa: E402


def program_key(graph: TaskGraph, plan: ExecutionPlan,
                impl: str) -> tuple[str, str, str]:
    """The process-wide cache key of a (graph, plan, impl) triple."""
    return (graph_fingerprint(graph), plan_fingerprint(plan), impl)


# ---------------------------------------------------------------------------
# Persistent compilation cache (cross-process artifact sharing)
# ---------------------------------------------------------------------------
#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path inside the checkout (listed in ``.gitignore``).  The path is part of
#: what a cache entry is found under, so it must not move between runs.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_persistent_dir: str | None = None


def compile_cache_dir() -> str:
    """The one place the compile-cache directory is resolved:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    :data:`DEFAULT_COMPILE_CACHE`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache at :func:`compile_cache_dir`
    — what entry points (``chip_smoke.py``, the examples) call at start."""
    return enable_persistent_cache(compile_cache_dir())


def enable_persistent_cache(path: str) -> str:
    """Point JAX's persistent compilation cache at ``path`` and open it up
    to every program this engine compiles (no min-size / min-compile-time
    cutoffs — plan programs are small but re-lowered by every process).
    When ``JAX_COMPILATION_CACHE_DIR`` names ``path`` already, JAX holds
    it and no directory is set here.

    Returns the directory so callers can log/inspect it.  Safe to call more
    than once; the last directory wins process-wide.
    """
    global _persistent_dir
    os.makedirs(path, exist_ok=True)
    # Crash hygiene before trusting the directory: a process killed
    # mid-write leaves zero-byte entries / orphaned temp files that would
    # otherwise surface as deserialization errors on the next warm start.
    # Scrubbed entries are simply recompiled (logged by the scrubber).
    from ..ft.artifacts import (ArtifactError, atomic_write_json,
                                load_json, quarantine_file, scrub_cache_dir)
    scrub_cache_dir(path)
    # Checksummed ownership metadata rides alongside the cache entries: a
    # torn/corrupt metadata file is quarantined and rewritten (never fatal
    # at startup), and a jax-version change is recorded — entries are keyed
    # by jax's own compilation fingerprint, so stale ones are merely dead
    # weight, not a correctness hazard.
    meta_path = os.path.join(path, "repro-cache-metadata.json")
    meta = {"schema": 1, "jax": jax.__version__}
    try:
        seen = load_json(meta_path, require_checksum=True)
        if seen != meta:
            atomic_write_json(meta_path, meta)
    except FileNotFoundError:
        atomic_write_json(meta_path, meta)
    except (ArtifactError, OSError) as exc:
        quarantine_file(meta_path, reason=repr(exc))
        atomic_write_json(meta_path, meta)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") != path:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if _persistent_dir != path:
        # jax latches the cache backend on first compile; a process that
        # already compiled anything would otherwise silently never persist
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    _persistent_dir = path
    return path


def persistent_cache_dir() -> str | None:
    """The active persistent-cache directory, if any."""
    return _persistent_dir


# ---------------------------------------------------------------------------
# Program segments
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Segment:
    """A contiguous run of tasks compiled into one executable.

    ``in_arrays`` are the env arrays the segment reads (external inputs or
    earlier segments' outputs); ``out_arrays`` are what later segments or
    the caller consume — materialized buffers at the executable boundary.
    """

    index: int
    tids: tuple[int, ...]
    in_arrays: tuple[str, ...]
    out_arrays: tuple[str, ...]


def _split_segments(schedule: WaveSchedule, lowered: dict[int, TaskLowering],
                    materialize: frozenset[str], out_names: tuple[str, ...],
                    ) -> list[Segment]:
    """Split the wave-major task order at multi-consumer producers.

    A task whose output feeds >= 2 consumer tasks closes its segment, so the
    output crosses an executable boundary and XLA cannot clone the producer
    into each consumer (see module docstring).  With no such producers the
    whole plan stays one segment, i.e. one executable.
    """
    order = schedule.order
    groups: list[list[int]] = []
    cur: list[int] = []
    for tid in order:
        cur.append(tid)
        if lowered[tid].out_array in materialize:
            groups.append(cur)
            cur = []
    if cur:
        groups.append(cur)

    segments: list[Segment] = []
    for gi, group in enumerate(groups):
        # external reads: arrays consumed before any in-segment write (an
        # in-segment write earlier in the group satisfies later reads, and
        # a task reading its own output array is a cross-task accumulation
        # seed, external only for the segment's first writer)
        seen: set[str] = set()
        ext: list[str] = []
        for tid in group:
            lw = lowered[tid]
            for a in lw.in_arrays:
                if a not in seen and a not in ext:
                    ext.append(a)
            seen.add(lw.out_array)
        later_reads = {a for g2 in groups[gi + 1:] for tid in g2
                       for a in lowered[tid].in_arrays}
        outs: list[str] = []
        for tid in group:
            a = lowered[tid].out_array
            if (a in later_reads or a in out_names) and a not in outs:
                outs.append(a)
        segments.append(Segment(index=gi, tids=tuple(group),
                                in_arrays=tuple(ext),
                                out_arrays=tuple(outs)))
    return segments


# ---------------------------------------------------------------------------
# The compiled program
# ---------------------------------------------------------------------------
class PlanProgram:
    """One plan, one impl, one compiled executable per segment.

    Most plans have a single segment (the PR-2 whole-program lowering); a
    plan with multi-consumer intermediates is split at those boundaries.
    ``pool_size`` > 1 clones the segment executables into a round-robin
    pool so repeated/concurrent calls spread over distinct executables.
    """

    def __init__(self, graph: TaskGraph, plan: ExecutionPlan, impl: str,
                 fg: FusedGraph | None = None,
                 schedule: WaveSchedule | None = None,
                 pool_size: int | None = None):
        self.graph = graph
        self.plan = plan
        self.impl = impl
        self.fg = fg if fg is not None else fuse(graph)
        self.schedule = schedule if schedule is not None \
            else wave_schedule(self.fg, plan)
        self.lowered: dict[int, TaskLowering] = {
            t.tid: lower_task(self.fg, t, plan.configs[t.tid], impl)
            for t in self.fg.tasks
        }
        self.in_names = tuple(graph.external_inputs())
        self.out_names = tuple(graph.final_outputs())
        # Task outputs feeding >= 2 consumer tasks: XLA CPU clones such
        # producers into every consumer fusion (observed on gemver — the
        # rank-2 update recomputed per consumer dot), through optimization
        # barriers and even past explicit outputs.  These arrays define the
        # segment boundaries where materialization is structural.
        consumers: dict[str, set[int]] = {}
        for (_, v, a) in self.fg.edges:
            consumers.setdefault(a, set()).add(v)
        self._materialize = frozenset(
            a for a, vs in consumers.items() if len(vs) >= 2)
        self._devices = tuple(jax.devices())
        self._multi = len(self._devices) > 1 and self.schedule.multi_slice
        self._traces = 0
        # one lock for the serving counters: concurrent submit threads
        # round-robin onto distinct clones (every call gets a unique
        # index) and `calls`/`trace_count` never lose updates
        self._counter_lock = threading.Lock()
        self._calls = 0
        # clones rotated out of round-robin (straggler mitigation): the
        # serving layer disables a persistently slow clone so requests
        # stop landing on it; at least one clone always stays enabled
        self._disabled: set[int] = set()
        if os.environ.get("REPRO_PROGRAM_SEGMENT", "1") == "0":
            # debug escape hatch: single-executable lowering, barrier-pinned
            self.segments = [Segment(0, tuple(self.schedule.order),
                                     self.in_names, self.out_names)]
        else:
            self.segments = _split_segments(
                self.schedule, self.lowered, self._materialize,
                self.out_names)
        self.pool_size = pool_size if pool_size is not None \
            else _env_int("REPRO_PROGRAM_POOL_SIZE", DEFAULT_POOL_SIZE)
        self._pool: list[tuple[Callable, ...]] = [
            tuple(jax.jit(self._segment_body(seg)) for seg in self.segments)
            for _ in range(self.pool_size)
        ]
        self._single = len(self.segments) == 1

    # -- introspection ----------------------------------------------------
    @property
    def trace_count(self) -> int:
        """How many times any segment body has been (re-)traced."""
        return self._traces

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def calls(self) -> int:
        """Requests served by this program (pool round-robin position is
        ``calls % pool_size``)."""
        return self._calls

    def entry(self):
        """Direct single-dispatch call info for single-segment,
        single-device programs: ``(in_arrays, out_arrays, body)`` where
        ``body(*vals)`` is the *untraced* segment body.

        Latency-critical wrappers (``TracedExecutable``) inline the body
        into their own single ``jax.jit`` together with const binding and
        output restoration, so one call costs exactly one jit dispatch —
        the per-call env dict, counter lock and pool rotation of
        ``__call__`` measured ~9us on the frontend benchmark, most of the
        remaining traced-vs-jit gap.  Returns ``None`` for multi-segment
        or multi-device programs (those need the env/transfer machinery).
        """
        if not self._single or self._multi:
            return None
        seg = self.segments[0]
        return seg.in_arrays, seg.out_arrays, self._segment_body(seg)

    def unit_kinds(self) -> dict[str, int]:
        """Lowered-unit census: plan-tiled ``contraction`` kernels vs
        ``einsum`` fallback vs frontend ``opaque`` passthrough segments —
        the program-side counterpart of a trace's coverage ratio."""
        out: dict[str, int] = {}
        for lw in self.lowered.values():
            for u in lw.units:
                out[u.kind] = out.get(u.kind, 0) + 1
        return out

    def lower(self, inputs: dict, sharding=None) -> list:
        """Each segment lowered (``jax.stages.Lowered``) for inputs of these
        shapes and dtypes (arrays or ``ShapeDtypeStruct``), every argument
        placed by ``sharding`` (default: the default device).  Compiled
        for a TPU, a Mosaic kernel shows as ``tpu_custom_call``."""
        def shape(v):
            return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)

        env = {k: shape(v) for k, v in inputs.items()}
        out = []
        for seg, fn in zip(self.segments, self._pool[0]):
            args = [env[a] for a in seg.in_arrays]
            out.append(fn.lower(*args))
            env.update(zip(seg.out_arrays,
                           map(shape, jax.eval_shape(fn, *args))))
        return out

    def est_bytes(self) -> int:
        """Rough resident-size estimate of this cache entry: the graph's
        array footprint once (intermediate buffers live inside the
        executables) plus a fixed per-task code estimate per pool clone."""
        arrays = sum(a.bytes for a in self.graph.arrays.values())
        code = 64 * 1024 * len(self.lowered) * self.pool_size
        return arrays + code

    def _dev(self, slice_id: int) -> int:
        return slice_id % len(self._devices)

    # -- traced bodies ----------------------------------------------------
    def _segment_body(self, seg: Segment):
        """Build the traceable body of one segment (closure per pool clone,
        so every ``jax.jit`` wrapper compiles its own executable)."""
        tids = frozenset(seg.tids)

        def body(*flat: jax.Array):
            with self._counter_lock:
                self._traces += 1
            env: dict[str, jax.Array] = dict(zip(seg.in_arrays, flat))
            placed: dict[tuple[str, int], jax.Array] = {}

            def on_device(array: str, d: int) -> jax.Array:
                key = (array, d)
                if key not in placed:
                    placed[key] = jax.device_put(env[array],
                                                 self._devices[d])
                return placed[key]

            for wi, wave in enumerate(self.schedule.waves):
                for tid in wave:
                    if tid not in tids:
                        continue
                    lw = self.lowered[tid]
                    if self._multi:
                        d = self._dev(self.schedule.slice_of[tid])
                        args = [on_device(a, d) for a in lw.in_arrays]
                    else:
                        args = [env[a] for a in lw.in_arrays]
                    out = lw.body(*args)
                    if self._single and lw.out_array in self._materialize \
                            and lw.out_array not in seg.out_arrays:
                        # unsegmented fallback: barrier-pin multi-consumer
                        # producers (best effort — see module docstring)
                        out = jax.lax.optimization_barrier(out)
                    if self._multi:
                        # the array has a new version: stale placements die
                        for k in [k for k in placed
                                  if k[0] == lw.out_array]:
                            del placed[k]
                    env[lw.out_array] = out
                if self._multi:
                    # Overlap-aware dispatch: cross-slice edges whose
                    # producer AND consumer live in this segment are issued
                    # at the producer's wave so the transfer rides under
                    # wave wi+1's compute.  Edges crossing a segment
                    # boundary are materialized there and placed at use.
                    for tr in self.schedule.transfers:
                        if tr.ready_wave == wi and tr.src in tids \
                                and tr.dst in tids:
                            on_device(tr.array, self._dev(tr.dst_slice))
            if self._multi:
                # final outputs land on device 0 (the PR-2 contract, kept
                # for every segment — a multi-consumer intermediate can
                # itself be a final output produced mid-program)
                outs = [jax.device_put(env[a], self._devices[0])
                        if a in self.out_names else env[a]
                        for a in seg.out_arrays]
            else:
                outs = [env[a] for a in seg.out_arrays]
            return tuple(outs)

        return body

    # -- pool-clone health (straggler rotation) ---------------------------
    def disable_clone(self, clone: int) -> bool:
        """Rotate a pool clone out of round-robin (persistently slow —
        see ``repro.ft.StragglerMonitor``).  Refuses to disable the last
        enabled clone; returns whether the clone is now disabled."""
        with self._counter_lock:
            if not 0 <= clone < self.pool_size:
                return False
            if len(self._disabled) >= self.pool_size - 1 \
                    and clone not in self._disabled:
                return False
            self._disabled.add(clone)
            return True

    def enable_clone(self, clone: int) -> None:
        with self._counter_lock:
            self._disabled.discard(clone)

    @property
    def disabled_clones(self) -> tuple[int, ...]:
        with self._counter_lock:
            return tuple(sorted(self._disabled))

    def _next_clone(self) -> int:
        with self._counter_lock:
            i = self._calls
            self._calls = i + 1
            if not self._disabled:
                return i % self.pool_size
            enabled = [c for c in range(self.pool_size)
                       if c not in self._disabled]
            return enabled[i % len(enabled)]

    # -- execution --------------------------------------------------------
    def run(self, inputs: dict[str, jax.Array]
            ) -> tuple[dict[str, jax.Array], int]:
        """Execute one request and report which pool clone served it —
        the serving layer's entry (clone-attributed timing feeds the
        straggler monitor)."""
        clone = self._next_clone()
        return self._run_on(inputs, self._pool[clone]), clone

    def _run_on(self, inputs: dict[str, jax.Array],
                fns: tuple[Callable, ...]) -> dict[str, jax.Array]:
        if self._single:
            seg = self.segments[0]
            outs = fns[0](*[inputs[a] for a in seg.in_arrays])
            return dict(zip(seg.out_arrays, outs))
        env = dict(inputs)
        for seg, fn in zip(self.segments, fns):
            res = fn(*[env[a] for a in seg.in_arrays])
            env.update(zip(seg.out_arrays, res))
        return {a: env[a] for a in self.out_names}

    def __call__(self, inputs: dict[str, jax.Array]) -> dict[str, jax.Array]:
        return self.run(inputs)[0]


# ---------------------------------------------------------------------------
# Process-wide bounded LRU program cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CacheEntry:
    """One cached program plus its serving statistics."""

    program: PlanProgram
    hits: int = 0
    last_use: float = 0.0
    est_bytes: int = 0

    def stats(self) -> dict:
        return {"hits": self.hits, "last_use": self.last_use,
                "est_bytes": self.est_bytes,
                "pool_size": self.program.pool_size,
                "n_segments": self.program.n_segments,
                "calls": self.program.calls}


class ProgramCache:
    """Bounded LRU cache of compiled plan programs — thread-safe.

    Keys are (graph fingerprint, plan fingerprint, impl).  A ``get`` moves
    the entry to the MRU position; inserting beyond ``capacity`` evicts the
    LRU entry (its jitted executables die with it once callers drop their
    references).

    Every operation holds ``lock`` (an RLock): concurrent ``submit``
    threads used to race the OrderedDict mutation in get/put (move_to_end
    during iteration, double evictions, lost hit counts).  Compilation
    itself happens *outside* this lock — see :func:`compiled_program` —
    so a slow build never stalls unrelated hits.
    """

    def __init__(self, capacity: int = DEFAULT_CACHE_SIZE):
        self.lock = threading.RLock()
        self.capacity = max(1, capacity)
        self._entries: OrderedDict[tuple, CacheEntry] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self.lock:
            return len(self._entries)

    def __contains__(self, key: tuple) -> bool:
        with self.lock:
            return key in self._entries

    def keys(self) -> list[tuple]:
        """LRU -> MRU order (eviction order is the front of this list)."""
        with self.lock:
            return list(self._entries)

    def entry(self, key: tuple) -> CacheEntry | None:
        """Peek an entry without touching LRU order or hit counts."""
        with self.lock:
            return self._entries.get(key)

    def get(self, key: tuple) -> PlanProgram | None:
        """Hit path: O(1), no fingerprinting — serving engines resolve a
        precomputed key here on every request."""
        with self.lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            entry.hits += 1
            entry.last_use = time.monotonic()
            self.hits += 1
            return entry.program

    def get_if(self, key: tuple, pool_size: int | None) -> PlanProgram | None:
        """Hit only when the cached program satisfies the caller's pool
        contract (``pool_size=None`` accepts any); a contract mismatch is
        not a hit — the caller will rebuild."""
        with self.lock:
            entry = self._entries.get(key)
            if entry is None or (pool_size is not None
                                 and entry.program.pool_size != pool_size):
                return None
            return self.get(key)

    def count_miss(self) -> None:
        with self.lock:
            self.misses += 1

    def put(self, key: tuple, program: PlanProgram) -> PlanProgram:
        with self.lock:
            self._entries[key] = CacheEntry(
                program=program, last_use=time.monotonic(),
                est_bytes=program.est_bytes())
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
            return program

    def invalidate(self, key: tuple) -> bool:
        """Drop one entry (quarantine path: a program whose outputs failed
        canary validation must not be served again — the next resolve
        rebuilds from scratch).  Not counted as an eviction; returns
        whether the key was present."""
        with self.lock:
            return self._entries.pop(key, None) is not None

    def resize(self, capacity: int) -> None:
        with self.lock:
            self.capacity = max(1, capacity)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1

    def clear(self) -> None:
        with self.lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0

    def stats(self, detail: bool = False) -> dict:
        with self.lock:
            out = {
                "size": len(self._entries),
                "capacity": self.capacity,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "est_bytes": sum(e.est_bytes
                                 for e in self._entries.values()),
            }
            if detail:
                out["entries"] = {"/".join(k): e.stats()
                                  for k, e in self._entries.items()}
            return out


_CACHE = ProgramCache(_env_int("REPRO_PROGRAM_CACHE_SIZE",
                               DEFAULT_CACHE_SIZE))


def program_cache() -> ProgramCache:
    """The process-wide program cache (shared by solver measurement, the
    executors and every ``PlanEngine`` replica in this process)."""
    return _CACHE


def set_program_cache_size(capacity: int) -> None:
    """Re-bound the process-wide cache, evicting LRU overflow."""
    _CACHE.resize(capacity)


# Per-key build locks: concurrent misses for the SAME program compile once
# (the second thread blocks, then hits), while different keys build in
# parallel.  The registry itself is guarded and bounded; clearing it only
# risks one duplicate build per cleared key, never corruption.
_BUILD_LOCKS: dict[tuple, threading.Lock] = {}
_BUILD_LOCKS_GUARD = threading.Lock()
_BUILD_LOCKS_MAX = 1024


def _build_lock(key: tuple) -> threading.Lock:
    with _BUILD_LOCKS_GUARD:
        lock = _BUILD_LOCKS.get(key)
        if lock is None:
            if len(_BUILD_LOCKS) >= _BUILD_LOCKS_MAX:
                _BUILD_LOCKS.clear()
            lock = _BUILD_LOCKS.setdefault(key, threading.Lock())
        return lock


def compiled_program(graph: TaskGraph, plan: ExecutionPlan, impl: str,
                     fg: FusedGraph | None = None,
                     schedule: WaveSchedule | None = None,
                     pool_size: int | None = None) -> PlanProgram:
    """Cache lookup/build: same (graph, plan, impl) -> same PlanProgram.

    A hit re-uses the program's lowerings AND its ``jax.jit`` trace caches,
    so a repeated call with identical input shapes/dtypes re-lowers and
    re-traces nothing.  An explicit ``pool_size`` differing from the cached
    entry rebuilds it (the pool is part of the execution contract).

    Thread-safe: cache bookkeeping happens under the cache lock, the build
    under a per-key lock (N threads missing the same cold program compile
    it once; distinct programs still compile concurrently).
    """
    key = program_key(graph, plan, impl)
    prog = _CACHE.get_if(key, pool_size)
    if prog is not None:
        return prog
    with _build_lock(key):
        prog = _CACHE.get_if(key, pool_size)    # built while we waited?
        if prog is not None:
            return prog
        _CACHE.count_miss()
        built = PlanProgram(graph, plan, impl, fg=fg, schedule=schedule,
                            pool_size=pool_size)
        return _CACHE.put(key, built)


def cache_stats(detail: bool = False) -> dict:
    """Global program-cache statistics (one source of truth for the bench
    gate and ``PlanEngine.stats()``): size/capacity, hits/misses/evictions,
    estimated bytes, and per-entry detail on request."""
    return _CACHE.stats(detail=detail)


def clear_program_cache() -> None:
    _CACHE.clear()
