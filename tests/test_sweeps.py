"""Sweep executor + structural-replay cache: determinism and reuse.

Three contracts pinned here:

* **Fork determinism** — ``sweep_execute`` with ``workers=4`` returns
  byte-identical results to ``workers=1`` AND to the legacy
  single-process ``fleet_sweep`` path, across every registered policy.
  The mechanism is the per-engine :class:`UidNamespace`: a fresh
  namespace reproduces exactly the uid streams ``reset_uid_counters()``
  rewinds the module counters to, so worker scheduling cannot perturb
  bloom seeding.
* **Cache soundness** — a :class:`StructuralCache` hit skips phase A
  and still returns bit-identical :class:`SimResult`\\ s to a fresh
  replay; the content key covers config, device, regions and op stream
  (a change to any of them misses) but NOT arrivals (every schedule
  shares the entry — that independence is the amortization).
* **Pad-plan reuse** — ``lindley_batch_np`` reuses its power-of-two
  bucketing plan and padded buffers across calls with the same length
  multiset, without leaking one call's payload into the next.
"""

import numpy as np
import pytest

from repro.core import (DeviceModel, Simulator, StructuralCache, SweepPoint,
                        UidNamespace, fleet_sweep, get_policy, point_key,
                        reset_uid_counters, run_point, serial_sweep,
                        serial_sweep_parallel, sweep_execute)
from repro.core.policies import resolve_names

SCALE = 1 << 17
DEV = DeviceModel.scaled(1 / 1024)
POLICIES = resolve_names("all")


def _workload(seed=3, n=5_000, read_frac=0.3):
    rng = np.random.default_rng(seed)
    ops = (rng.random(n) < read_frac).astype(np.uint8)
    keys = rng.integers(0, SCALE, n).astype(np.int64)
    return ops, keys


def _points(policies, shard_counts=(1,), rates=(3_000.0, 12_000.0), n=5_000):
    ops, keys = _workload(n=n)
    grid = [np.arange(n, dtype=np.float64) / r for r in rates]
    return [SweepPoint(label=f"{p}/{k}",
                       cfg=get_policy(p).default_config(scale=SCALE)
                       .with_(n_shards=k),
                       device=DEV, op_types=ops, keys=keys,
                       arrivals_grid=grid)
            for p in policies for k in shard_counts]


def _assert_identical(a, b):
    """Byte-identity, not tolerance: same uid streams, same arithmetic."""
    assert np.array_equal(a.latency, b.latency)
    assert np.array_equal(a.get_reads, b.get_reads)
    assert np.array_equal(a.get_probed, b.get_probed)
    assert a.n_stalls == b.n_stalls
    assert a.stall_events == b.stall_events


# ------------------------------------------------------ fork determinism

def test_workers_byte_parity_all_policies():
    """Every registered policy through the executor: workers=4 equals
    workers=1 equals the legacy fleet_sweep path, byte for byte."""
    points = _points(POLICIES)
    r1, t1 = sweep_execute(points, workers=1)
    r4, t4 = sweep_execute(points, workers=4)
    legacy = fleet_sweep(points, backend="numpy")
    assert len(r1) == len(r4) == len(legacy) == len(points)
    for p1, p4, pl in zip(r1, r4, legacy):
        for a, b, c in zip(p1, p4, pl):
            _assert_identical(a, b)
            _assert_identical(a, c)
    assert [t.label for t in t1] == [t.label for t in t4] \
        == [p.label for p in points]


def test_serial_sweep_parallel_matches_serial_sweep():
    """The heap-loop oracle under the pool: namespace-built engines over
    flattened (point, rate) tasks reproduce serial_sweep exactly."""
    points = _points(("vlsm", "rocksdb"), shard_counts=(1, 2))
    sp1 = serial_sweep_parallel(points, workers=1)
    sp4 = serial_sweep_parallel(points, workers=4)
    legacy = serial_sweep(points)
    for g1, g4, gl in zip(sp1, sp4, legacy):
        assert len(g1) == len(g4) == len(gl)
        for a, b, c in zip(g1, g4, gl):
            _assert_identical(a, b)
            _assert_identical(a, c)


def test_namespace_equals_reset_counters():
    """The foundation: a fresh UidNamespace reproduces the module-counter
    stream reset_uid_counters() rewinds to — same blooms, same bytes."""
    cfg = get_policy("vlsm").default_config(scale=SCALE).with_(n_shards=2)
    ops, keys = _workload()
    arr = np.arange(ops.shape[0], dtype=np.float64) / 5_000.0
    reset_uid_counters()
    r_mod = Simulator(cfg, DEV).run(ops, keys, arr)
    r_ns = Simulator(cfg, DEV, uids=UidNamespace()).run(ops, keys, arr)
    _assert_identical(r_mod, r_ns)


# -------------------------------------------------------- cache keying

def test_point_key_ignores_arrivals_and_label():
    points = _points(("vlsm",))
    alt = _points(("vlsm",), rates=(7_000.0,))
    alt[0].label = "renamed"
    assert point_key(points[0]) == point_key(alt[0])


def test_point_key_covers_cfg_device_and_stream():
    base = _points(("vlsm",))[0]
    k0 = point_key(base)

    recfg = _points(("vlsm",), shard_counts=(2,))[0]
    assert point_key(recfg) != k0

    other_policy = _points(("rocksdb",))[0]
    assert point_key(other_policy) != k0

    redev = SweepPoint(label=base.label, cfg=base.cfg,
                       device=DeviceModel.scaled(1 / 2048),
                       op_types=base.op_types, keys=base.keys,
                       arrivals_grid=base.arrivals_grid)
    assert point_key(redev) != k0

    rekeys = SweepPoint(label=base.label, cfg=base.cfg, device=DEV,
                        op_types=base.op_types,
                        keys=(base.keys + 1).astype(np.int64),
                        arrivals_grid=base.arrivals_grid)
    assert point_key(rekeys) != k0


def test_cache_hit_misses_and_invalidation():
    cache = StructuralCache()
    pt = _points(("vlsm",))[0]
    _, t_miss = run_point(pt, cache=cache)
    assert not t_miss.cache_hit and t_miss.structural_s > 0.0
    assert cache.stats()["misses"] == 1 and cache.stats()["hits"] == 0

    _, t_hit = run_point(pt, cache=cache)
    assert t_hit.cache_hit and t_hit.structural_s == 0.0
    assert cache.stats()["hits"] == 1

    # a config change is a different content address: fresh phase A
    recfg = _points(("vlsm",), shard_counts=(2,))[0]
    _, t2 = run_point(recfg, cache=cache)
    assert not t2.cache_hit
    assert cache.stats()["misses"] == 2 and len(cache) == 2

    # a stream change likewise
    restream = SweepPoint(label=pt.label, cfg=pt.cfg, device=DEV,
                          op_types=pt.op_types,
                          keys=(pt.keys + 1).astype(np.int64),
                          arrivals_grid=pt.arrivals_grid)
    _, t3 = run_point(restream, cache=cache)
    assert not t3.cache_hit and len(cache) == 3


def test_cache_hit_is_bit_identical_to_fresh_replay():
    """The correctness gate: a cached engine's temporal passes return
    the exact results a fresh structural replay would."""
    cache = StructuralCache()
    pt = _points(("vlsm",), shard_counts=(2,))[0]
    miss_res, _ = run_point(pt, cache=cache)
    hit_res, t = run_point(pt, cache=cache)
    assert t.cache_hit
    fresh_res, _ = run_point(pt, cache=None)
    for a, b, c in zip(hit_res, miss_res, fresh_res):
        _assert_identical(a, b)
        _assert_identical(a, c)


def test_cache_lru_eviction():
    cache = StructuralCache(maxsize=2)
    pts = _points(("vlsm", "rocksdb", "lazy"), n=2_000)
    keys = [point_key(p) for p in pts]
    for p in pts[:2]:
        run_point(p, cache=cache)
    run_point(pts[0], cache=cache)           # refresh pts[0]'s recency
    run_point(pts[2], cache=cache)           # evicts pts[1], the LRU
    assert len(cache) == 2
    assert keys[0] in cache and keys[2] in cache
    assert keys[1] not in cache


# ------------------------------------------------ one process per chip

@pytest.mark.parametrize("tier", ["lindley", "merge", "index", "cfg_index"])
def test_fork_pool_refuses_device_tiers(tier):
    """workers > 1 with a device tier selected raises before any fork:
    the chip belongs to one process, and a forked child of a parent
    that touched JAX fails or hangs on it."""
    from repro.core import level_index, merge
    from repro.core.sweeps import parallel_map
    points = _points(POLICIES[:1], n=200)
    backend = "jnp" if tier == "lindley" else "numpy"
    if tier == "cfg_index":
        points[0].cfg = points[0].cfg.with_(index_backend="pallas")
    switch = {"merge": merge, "index": level_index}.get(tier)
    try:
        if switch is not None:
            switch.set_backend("pallas")
        with pytest.raises(ValueError, match="device tier"):
            sweep_execute(points, workers=2, backend=backend)
        if tier != "lindley":
            with pytest.raises(ValueError, match="device tier"):
                serial_sweep_parallel(points, workers=2)
        if switch is not None:
            with pytest.raises(ValueError, match="device tier"):
                parallel_map(abs, [1, -2], workers=2)
    finally:
        if switch is not None:
            switch.set_backend("numpy")
    # one process is always allowed
    r1, _ = sweep_execute(points, workers=1, backend="numpy")
    assert len(r1) == 1


# ----------------------------------------------------- pad-plan caching

def test_lindley_pad_plan_reused_across_calls():
    from repro.kernels.lindley_scan import ops as lops
    lops.clear_pad_plans()
    lens = (700, 700, 300, 90)
    rng = np.random.default_rng(5)
    svc = [rng.random(n) for n in lens]
    arr = [np.sort(rng.random(n)) * 10 for n in lens]
    plan_a = lops._pad_plan(lens)
    out1 = lops.lindley_batch_np(arr, svc, backend="jnp")
    plan_b = lops._pad_plan(lens)
    assert plan_a is plan_b                  # LRU returns the same plan

    # second call with DIFFERENT payloads through the same buffers:
    # no state leaks — each departure equals its own fresh computation
    svc2 = [rng.random(n) for n in lens]
    arr2 = [np.sort(rng.random(n)) * 10 for n in lens]
    out2 = lops.lindley_batch_np(arr2, svc2, backend="jnp")
    lops.clear_pad_plans()
    fresh2 = lops.lindley_batch_np(arr2, svc2, backend="jnp")
    fresh1 = lops.lindley_batch_np(arr, svc, backend="jnp")
    for got, want in zip(out2 + out1, fresh2 + fresh1):
        assert np.array_equal(got, want)


def test_lindley_numpy_scratch_growth():
    from repro.kernels.lindley_scan import ops as lops
    rng = np.random.default_rng(9)
    small = [rng.random(50) for _ in range(3)]
    arr_s = [np.sort(rng.random(50)) * 10 for _ in range(3)]
    big = [rng.random(5_000)]
    arr_b = [np.sort(rng.random(5_000)) * 10]
    o_small = lops.lindley_batch_np(arr_s, small, backend="numpy")
    o_big = lops.lindley_batch_np(arr_b, big, backend="numpy")
    o_small2 = lops.lindley_batch_np(arr_s, small, backend="numpy")
    for got, want in zip(o_small, o_small2):
        assert np.array_equal(got, want)
    assert o_big[0].shape == (5_000,)
