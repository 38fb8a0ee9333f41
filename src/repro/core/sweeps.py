"""Parallel sweep executor + content-addressed structural-replay cache.

A sweep matrix is a list of :class:`~repro.core.fleet.SweepPoint`\\ s;
PR 6's two-phase engine already amortizes the expensive structural
replay (phase A) over each point's arrival grid.  This layer adds the
two remaining amortizations:

* **Across processes** — :func:`sweep_execute` dispatches points over a
  fork-based worker pool.  Every engine is built with its own
  :class:`~repro.core.uids.UidNamespace`, so worker interleaving cannot
  perturb any uid stream: a fresh namespace starts from exactly the
  state ``reset_uid_counters()`` rewinds the module counters to, which
  makes the parallel rows byte-identical to the single-process path
  (``tests/test_sweeps.py`` pins workers=1 vs workers=4 across every
  registered policy).
* **Across calls** — :class:`StructuralCache` stores PREPARED engines
  (phase A done) under a content address: blake2b over the canonicalized
  ``LSMConfig`` (policy name included), the ``DeviceModel``, the region
  count and the raw op-stream bytes.  A hit skips phase A entirely and
  goes straight to ``temporal_pass`` + Lindley — sound because a
  temporal pass resets ALL pass-local state (the same mechanism
  ``traffic_curve`` relies on), so a cached engine returns the exact
  :class:`~repro.core.fleet.PendingRun` structures a fresh replay would.
  Arrival schedules are deliberately NOT part of the key: structure is
  arrival-independent (fleet.py's observation 2) — that independence is
  the amortization.

Every :func:`run_point` call reports per-phase wall-clock
(:class:`PointTiming`: ``structural_s`` / ``temporal_s`` / ``lindley_s``
/ ``finalize_s``) so the bench rows carry the win, and the module
:data:`LEDGER` accumulates executor wall vs summed per-task compute for
the machine-readable ``perf_trajectory`` row in BENCH_dbbench.json.

Forked workers inherit the parent's cache copy-on-write (hits on
pre-warmed entries are free); their own ``put``\\ s stay in the child,
so cross-point reuse inside one ``sweep_execute`` call only happens
when two points land on the same worker — the in-process ``workers=1``
path sees every hit.

The pool is for the numpy tier only.  A chip belongs to one process, and
a child forked from a parent that has touched JAX fails or hangs when it
needs the device, so ``workers > 1`` with a device tier selected (a
``jnp``/``pallas`` Lindley backend, merge backend or manifest backend)
raises instead of forking.
"""

from __future__ import annotations

import dataclasses
import hashlib
import multiprocessing
import time
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from . import level_index, merge
from .fleet import FleetEngine, SweepPoint
from .sim import SimResult, Simulator
from .uids import UidNamespace


# ------------------------------------------------------------- content key

def _digest_array(h, arr: np.ndarray | None) -> None:
    if arr is None:
        h.update(b"<none>")
        return
    a = np.ascontiguousarray(arr)
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())


def point_key(point: SweepPoint) -> str:
    """Content address of a point's *structural* identity.

    Covers everything phase A depends on — policy name (an ``LSMConfig``
    field), the full canonicalized config, the device model, the region
    count and the op-stream arrays (types / keys / scan lens, raw
    bytes).  Arrivals are excluded on purpose: the structural replay is
    arrival-independent, so every schedule shares the cached engine.
    ``blake2b`` rather than builtin ``hash``: stable across processes
    and runs (the determinism contract ``repro-lint`` enforces).
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(repr(sorted(dataclasses.asdict(point.cfg).items())).encode())
    h.update(repr(sorted(dataclasses.asdict(point.device).items())).encode())
    h.update(str(int(point.n_regions)).encode())
    _digest_array(h, point.op_types)
    _digest_array(h, point.keys)
    _digest_array(h, point.scan_lens)
    return h.hexdigest()


# ------------------------------------------------------------------ cache

class StructuralCache:
    """Bounded LRU of prepared :class:`FleetEngine`\\ s, content-keyed.

    A ``get`` hit returns an engine whose phase A already ran for the
    exact (config, device, regions, op stream) content — safe to run
    ``temporal_pass`` on directly.  Entries hold the engine's full
    structural state (plans, pre-ranked batches, trees), so the default
    capacity is small; eviction is LRU.
    """

    def __init__(self, maxsize: int = 8):
        self.maxsize = maxsize
        self._entries: OrderedDict[str, FleetEngine] = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key: str) -> FleetEngine | None:
        eng = self._entries.get(key)
        if eng is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return eng

    def put(self, key: str, eng: FleetEngine) -> None:
        self._entries[key] = eng
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: str) -> bool:
        return key in self._entries

    def stats(self) -> dict:
        return {"size": len(self._entries), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses}


#: the process-default cache ``run_point`` callers may share
DEFAULT_CACHE = StructuralCache()


# ----------------------------------------------------------------- timing

@dataclass
class PointTiming:
    """Per-phase wall-clock of one executed point.

    ``structural_s`` is phase A (0.0 on a cache hit); the three lists
    are per-grid-schedule (temporal pass, Lindley scan, finalize).
    """

    label: str
    cache_hit: bool
    structural_s: float
    temporal_s: list[float] = field(default_factory=list)
    lindley_s: list[float] = field(default_factory=list)
    finalize_s: list[float] = field(default_factory=list)

    @property
    def total_s(self) -> float:
        """The point's whole compute (the serial-equivalent cost this
        task would contribute to a single-process run)."""
        return self.structural_s + sum(self.temporal_s) \
            + sum(self.lindley_s) + sum(self.finalize_s)

    def row(self, i: int) -> dict:
        """Phase-timing fragment for the point's i-th grid row.  Phase A
        is attributed to the first row only, so summing a point's rows
        never double-counts the shared structural replay."""
        return {
            "structural_s": round(self.structural_s if i == 0 else 0.0, 6),
            "temporal_s": round(self.temporal_s[i], 6),
            "lindley_s": round(self.lindley_s[i], 6),
            "finalize_s": round(self.finalize_s[i], 6),
            "cache_hit": bool(self.cache_hit),
        }


@dataclass
class ExecutorLedger:
    """Per-process running totals of executor activity.

    ``wall_s`` is executor wall-clock; ``task_s`` the summed per-task
    compute — what the same tasks would cost serially in one process —
    so ``speedup`` is the pool+cache win the ``perf_trajectory`` bench
    row records.
    """

    wall_s: float = 0.0
    task_s: float = 0.0
    tasks: int = 0
    cache_hits: int = 0
    cache_misses: int = 0

    def add(self, *, wall_s: float, timings: list[PointTiming]) -> None:
        self.wall_s += wall_s
        for t in timings:
            self.task_s += t.total_s
            self.tasks += 1
            if t.cache_hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1

    @property
    def speedup(self) -> float:
        return self.task_s / max(self.wall_s, 1e-9)

    def reset(self) -> None:
        self.wall_s = 0.0
        self.task_s = 0.0
        self.tasks = 0
        self.cache_hits = 0
        self.cache_misses = 0


#: accumulates across every sweep_execute / bench helper in the process
LEDGER = ExecutorLedger()


# -------------------------------------------------------------- run_point

def run_point(point: SweepPoint, *, backend: str = "numpy",
              cache: StructuralCache | None = None
              ) -> tuple[list[SimResult], PointTiming]:
    """Evaluate one sweep point: phase A (or a cache hit), then one
    temporal pass + Lindley + finalize per schedule in ``point.grid``.

    The engine is built with a fresh :class:`UidNamespace`, making the
    results byte-identical to the legacy ``reset_uid_counters()`` +
    module-counter path regardless of what else the process has run.
    Returns the per-schedule results and the point's :class:`PointTiming`.
    """
    from repro.kernels.lindley_scan.ops import lindley_batch_np
    key = point_key(point)
    eng = cache.get(key) if cache is not None else None
    hit = eng is not None
    structural = 0.0
    if eng is None:
        t0 = time.perf_counter()
        eng = FleetEngine(point.cfg, point.device,
                          n_regions=point.n_regions, uids=UidNamespace())
        eng.prepare_structural(point.op_types, point.keys, point.scan_lens)
        structural = time.perf_counter() - t0
        if cache is not None:
            cache.put(key, eng)
    timing = PointTiming(label=point.label, cache_hit=hit,
                         structural_s=structural)
    results: list[SimResult] = []
    for arr in point.grid:
        t0 = time.perf_counter()
        pd = eng.temporal_pass(arr)
        t1 = time.perf_counter()
        deps = lindley_batch_np([q[0] for q in pd.queues],
                                [q[1] for q in pd.queues], backend=backend)
        t2 = time.perf_counter()
        results.append(eng.finalize(deps, pending=pd))
        t3 = time.perf_counter()
        timing.temporal_s.append(t1 - t0)
        timing.lindley_s.append(t2 - t1)
        timing.finalize_s.append(t3 - t2)
    return results, timing


# ---------------------------------------------------------- fork-pool map

# Fork-inherited task state: set immediately before Pool creation so the
# children receive it copy-on-write (no per-task pickling of the big
# op-stream arrays); tasks are plain indices into it.
_FORK_STATE: tuple | None = None


def _point_task(i: int) -> tuple[list[SimResult], PointTiming]:
    points, backend, cache = _FORK_STATE
    return run_point(points[i], backend=backend, cache=cache)


def _serial_task(task: tuple[int, int]) -> SimResult:
    pi, ai = task
    points = _FORK_STATE[0]
    p = points[pi]
    sim = Simulator(p.cfg, p.device, n_regions=p.n_regions,
                    uids=UidNamespace())
    return sim.run(p.op_types, p.keys, p.grid[ai], p.scan_lens)


def _check_fork_safe(workers: int, backend: str = "numpy",
                    points: list[SweepPoint] = ()) -> None:
    """Raise if ``workers > 1`` would fork workers while a device tier is
    selected: the chip belongs to the one process that touched it."""
    if workers <= 1:
        return
    tiers = {"lindley": backend, "merge": merge.get_backend(),
             "index": level_index.get_backend()}
    tiers.update({f"index[{p.label}]": p.cfg.index_backend
                  for p in points if p.cfg.index_backend is not None})
    device = {k: v for k, v in tiers.items() if v != "numpy"}
    if device:
        raise ValueError(f"workers={workers} would fork processes with a "
                         f"device tier selected ({device}); the device "
                         "tiers run in one process: use workers=1")


def _fork_map(fn, tasks: list, workers: int) -> list:
    ctx = multiprocessing.get_context("fork")
    with ctx.Pool(processes=min(workers, len(tasks))) as pool:
        return pool.map(fn, tasks)


def parallel_map(fn, items, *, workers: int = 1) -> list:
    """Order-preserving map with an optional fork pool.

    ``fn`` must be a module-level callable and ``items`` picklable when
    ``workers > 1`` (standard ``multiprocessing`` contract); ``workers
    <= 1`` is a plain in-process loop with no pool, no pickling.
    """
    _check_fork_safe(workers)
    items = list(items)
    if workers <= 1 or len(items) <= 1:
        return [fn(x) for x in items]
    return _fork_map(fn, items, workers)


# -------------------------------------------------------------- executors

def sweep_execute(points: list[SweepPoint], *, workers: int = 1,
                  backend: str = "numpy",
                  cache: StructuralCache | None = None
                  ) -> tuple[list[list[SimResult]], list[PointTiming]]:
    """Evaluate a sweep matrix through the executor.

    ``workers <= 1`` runs every point in-process (cache hits fully
    visible); ``workers > 1`` dispatches whole points over a fork pool —
    deterministic regardless of scheduling because every engine draws
    from its own uid namespace.  Returns ``(results, timings)`` with
    ``results[p]`` aligned to ``points[p].grid`` exactly like
    :func:`repro.core.fleet.fleet_sweep`, rows byte-identical to it.
    """
    global _FORK_STATE
    _check_fork_safe(workers, backend, points)
    t0 = time.perf_counter()
    if workers <= 1 or len(points) <= 1:
        pairs = [run_point(p, backend=backend, cache=cache) for p in points]
    else:
        _FORK_STATE = (list(points), backend, cache)
        try:
            pairs = _fork_map(_point_task, list(range(len(points))),
                              workers)
        finally:
            _FORK_STATE = None
    wall = time.perf_counter() - t0
    results = [r for r, _ in pairs]
    timings = [t for _, t in pairs]
    LEDGER.add(wall_s=wall, timings=timings)
    return results, timings


def serial_sweep_parallel(points: list[SweepPoint], *,
                          workers: int = 1) -> list[list[SimResult]]:
    """:func:`repro.core.fleet.serial_sweep` (the heap-loop oracle, full
    structural replay per (point, rate)) with namespace-built engines
    and an optional fork pool over the flattened (point, rate) tasks.
    Byte-identical results to ``serial_sweep`` — the namespace ≡ reset
    equivalence — in the same per-point grouping."""
    global _FORK_STATE
    _check_fork_safe(workers, points=points)
    tasks = [(pi, ai) for pi, p in enumerate(points)
             for ai in range(len(p.grid))]
    _FORK_STATE = (list(points),)
    try:
        if workers <= 1 or len(tasks) <= 1:
            flat = [_serial_task(t) for t in tasks]
        else:
            flat = _fork_map(_serial_task, tasks, workers)
    finally:
        _FORK_STATE = None
    out: list[list[SimResult]] = []
    k = 0
    for p in points:
        n = len(p.grid)
        out.append(flat[k:k + n])
        k += n
    return out
