"""LevelIndex manifest tests: backend parity (numpy / jnp / pallas),
batched-GET equivalence with the scalar path, and mirror consistency."""

import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:
    from _propshim import HealthCheck, given, settings, st

from repro.core import DeviceModel, LSMConfig, Simulator
from repro.core import level_index
from repro.core.level_index import (LevelIndex, bloom_false_positives,
                                    bloom_seed_for_uid)
from repro.core.sst import SST, overlapping

CFG = LSMConfig.vlsm_default(scale=1 << 16)


def _mk_level(rng, n_ssts, keys_per=8):
    """A sorted, pairwise-disjoint level of n_ssts SSTs with random gaps."""
    out = []
    base = 0
    for _ in range(n_ssts):
        base += int(rng.integers(1, 50))
        ks = np.sort(rng.choice(np.arange(base, base + 200), size=keys_per,
                                replace=False)).astype(np.int64)
        out.append(SST(ks, np.zeros(keys_per, np.int64), 100))
        base = int(ks[-1]) + 1
    return out


def _queries(rng, n, hi_key):
    lo = rng.integers(-5, hi_key + 5, size=n).astype(np.int64)
    width = rng.integers(0, 60, size=n).astype(np.int64)
    return lo, lo + width


@pytest.mark.parametrize("n_ssts", [0, 1, 7, 64])
def test_backends_agree_on_overlap_queries(n_ssts):
    """numpy / jnp / pallas LevelIndex queries agree on random fence sets,
    including empty and single-SST levels."""
    rng = np.random.default_rng(42 + n_ssts)
    ssts = _mk_level(rng, n_ssts)
    idx = LevelIndex(2)
    idx.refresh(1, ssts)
    hi_key = int(ssts[-1].largest) if ssts else 100
    lo, hi = _queries(rng, 40, hi_key)
    ref = None
    for backend in ("numpy", "jnp", "pallas"):
        level_index.set_backend(backend)
        try:
            got = (*idx.overlap_ranges(1, lo, hi),
                   idx.overlap_counts(1, lo, hi))
        finally:
            level_index.set_backend("numpy")
        if ref is None:
            ref = got
        else:
            for a, b in zip(ref, got):
                assert np.array_equal(a, b), f"{backend} differs from numpy"
    # and the numpy answer matches the list-walking oracle
    starts, ends, counts = ref
    for i in range(lo.shape[0]):
        want = overlapping(ssts, int(lo[i]), int(hi[i]))
        got_slice = ssts[int(starts[i]):int(ends[i])]
        assert got_slice == want
        assert int(counts[i]) == len(want)


def _level_from_ranges(ranges):
    """A level of SSTs with the given inclusive [smallest, largest] key
    ranges (a one-key SST where they are equal)."""
    return [SST(np.unique(np.asarray([a, b], np.int64)),
                np.zeros(1 if a == b else 2, np.int64), 100)
            for a, b in ranges]


#: sorted, disjoint levels whose fences tie or touch: one-key SSTs
#: (smallest == largest) and adjacent SSTs (largest + 1 == next smallest)
EDGE_LEVELS = {
    "one_sst_one_key": [(10, 10)],
    "one_key_ssts": [(10, 10), (12, 12), (13, 13), (20, 20)],
    "adjacent": [(10, 14), (15, 15), (16, 30), (31, 40)],
    "mixed": [(3, 3), (4, 9), (11, 11), (12, 19), (20, 20), (25, 60),
              (61, 61)],
}


def _edge_queries(ranges):
    """Points at each fence, at fence +- 1 and below and above the whole
    level, then every pair of them as a range."""
    fences = sorted({f for r in ranges for f in r})
    pts = sorted({p for f in fences for p in (f - 1, f, f + 1)}
                 | {0, fences[-1] + 5})
    pairs = [(a, b) for i, a in enumerate(pts) for b in pts[i:]]
    lo, hi = (np.asarray(c, np.int64) for c in zip(*pairs))
    return lo, hi


def _walk(ssts, lo, hi):
    """The list-walking oracle: every SST whose range meets [lo, hi]."""
    return [s for s in ssts if s.largest >= lo and s.smallest <= hi]


@pytest.mark.parametrize("backend", ["numpy", "jnp", "pallas"])
@pytest.mark.parametrize("shape", sorted(EDGE_LEVELS))
def test_backends_agree_on_fence_edge_queries(shape, backend):
    """One rank over the interleaved fence table answers both ends of an
    overlap query exactly where fences tie (one-key SSTs) or touch
    (adjacent SSTs), at every fence, fence +- 1 and beyond the level, in
    overlap_ranges, overlap_counts, overlap_slice and overlap_bytes."""
    ranges = EDGE_LEVELS[shape]
    ssts = _level_from_ranges(ranges)
    lo, hi = _edge_queries(ranges)
    idx = LevelIndex(3)
    idx.refresh(2, ssts)
    # level 1 holds the queries as SSTs, for overlap_bytes(1, 2)
    idx.refresh(1, _level_from_ranges(zip(lo.tolist(), hi.tolist())))
    level_index.set_backend(backend)
    try:
        starts, ends = idx.overlap_ranges(2, lo, hi)
        counts = idx.overlap_counts(2, lo, hi)
        obytes = idx.overlap_bytes(1, 2)
        slices = [idx.overlap_slice(2, int(a), int(b))
                  for a, b in zip(lo[::7], hi[::7])]
    finally:
        level_index.set_backend("numpy")
    for i in range(lo.shape[0]):
        want = _walk(ssts, int(lo[i]), int(hi[i]))
        assert ssts[int(starts[i]):int(ends[i])] == want, (lo[i], hi[i])
        assert int(counts[i]) == len(want)
        assert int(obytes[i]) == sum(s.size for s in want)
    for (a, b), (s, e) in zip(zip(lo[::7], hi[::7]), slices):
        assert ssts[s:e] == _walk(ssts, int(a), int(b))


def _profiled(trace_dir, fn):
    """Run ``fn`` under a JAX profiler session (the recorder is on only
    then); return the recorder's counters and the names of the host
    spans the profiler's trace holds, one entry per span."""
    import jax
    from repro import obs
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    counters = obs.counters()
    obs.reset()
    (path,) = trace_dir.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted(e.name for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name in ("manifest.rank", "fence_rank.call"))
    return counters, spans


def test_an_overlap_query_is_one_fence_rank_round_trip(tmp_path):
    """On pallas, one overlap_ranges call of n queries is one device call
    of 2n ranks: both ends come from the same rank of the fence table."""
    rng = np.random.default_rng(4)
    idx = LevelIndex(2, backend="pallas")
    idx.refresh(1, _mk_level(rng, 20))
    lo, hi = _queries(rng, 37, int(idx.largest[1][-1]))
    counters, spans = _profiled(
        tmp_path, lambda: idx.overlap_ranges(1, lo, hi))
    assert counters["fence_rank.calls"] == 1
    assert counters["fence_rank.queries"] == 2 * lo.shape[0]
    assert spans == ["fence_rank.call", "manifest.rank"]


def test_an_empty_level_makes_no_fence_rank_call(tmp_path):
    idx = LevelIndex(3, backend="pallas")
    lo = np.asarray([0, 5, 100], np.int64)
    out = {}
    counters, spans = _profiled(
        tmp_path, lambda: out.update(r=idx.overlap_ranges(2, lo, lo + 3)))
    assert "fence_rank.calls" not in counters
    assert "fence_rank.call" not in spans
    starts, ends = out["r"]
    assert starts.tolist() == ends.tolist() == [0, 0, 0]


def test_overlap_bytes_matches_bruteforce():
    rng = np.random.default_rng(3)
    src = _mk_level(rng, 12)
    dst = _mk_level(rng, 30)
    idx = LevelIndex(3)
    idx.refresh(1, src)
    idx.refresh(2, dst)
    ob = idx.overlap_bytes(1, 2)
    for i, s in enumerate(src):
        want = sum(d.size for d in overlapping(dst, s.smallest, s.largest))
        assert int(ob[i]) == want


def _build_tree(seed, n_ops=2500, cfg=CFG):
    rng = np.random.default_rng(seed)
    sim = Simulator(cfg, DeviceModel.scaled(1 / 1024))
    keys = rng.integers(0, 800, size=n_ops).astype(np.int64)
    sim.run(np.zeros(n_ops, np.uint8), keys,
            np.arange(n_ops, dtype=np.float64) / 1e4)
    return sim.trees[0]


@given(st.integers(0, 2**32))
@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_get_batch_equals_scalar_get(seed):
    """Property: get_batch == looped scalar get — seqs, reads AND probed."""
    tree = _build_tree(seed)
    rng = np.random.default_rng(seed + 1)
    queries = np.concatenate([
        rng.integers(0, 800, size=300),       # mostly hits
        rng.integers(10**6, 10**9, size=100),  # misses
    ]).astype(np.int64)
    b_seqs, b_reads, b_probed = tree.get_batch(queries)
    for i, k in enumerate(queries.tolist()):
        seq, reads, probed = tree.get(k)
        assert (seq if seq is not None else -1) == int(b_seqs[i])
        assert reads == int(b_reads[i])
        assert probed == int(b_probed[i])


def test_get_batch_pallas_backend_drop_in():
    """The pallas fence-rank kernel is a drop-in for the lookup path."""
    tree = _build_tree(7, n_ops=1500)
    rng = np.random.default_rng(8)
    queries = np.concatenate([rng.integers(0, 800, size=128),
                              rng.integers(10**6, 10**9, size=64)]
                             ).astype(np.int64)
    ref = tree.get_batch(queries)
    for backend in ("jnp", "pallas"):
        level_index.set_backend(backend)
        try:
            got = tree.get_batch(queries)
        finally:
            level_index.set_backend("numpy")
        for a, b in zip(ref, got):
            assert np.array_equal(a, b), f"{backend} lookup differs"


def test_per_store_index_backend_config():
    """LSMConfig.index_backend pins one store's manifest queries to an
    array backend regardless of the module-level switch."""
    cfg = CFG.with_(index_backend="jnp")
    tree = _build_tree(5, n_ops=1200, cfg=cfg)
    assert tree.index.backend == "jnp"
    ref_tree = _build_tree(5, n_ops=1200, cfg=CFG)
    rng = np.random.default_rng(6)
    queries = rng.integers(0, 800, size=200).astype(np.int64)
    got = tree.get_batch(queries)
    want = ref_tree.get_batch(queries)
    # NOTE: uids differ between the two trees (global counter), so bloom
    # false positives may differ — compare the found seqs only.
    assert np.array_equal(got[0], want[0])


def test_index_stays_in_lockstep_with_levels():
    """Incremental maintenance (flush, splice, uid-removal) never drifts
    from the SST lists, across every registered policy."""
    from repro.core.policies import default_configs
    for cfg in default_configs(scale=1 << 16).values():
        tree = _build_tree(11, n_ops=3000, cfg=cfg)
        tree.index.check_against(tree.levels)


def test_bloom_seed_matches_scalar_hash():
    keys = np.array([5, 12345, 2**47 + 3], np.int64)
    uid = 917
    want = [((int(k) * 0x9E3779B97F4A7C15 + uid * 0xBF58476D1CE4E5B9)
             & 0xFFFFFFFF) / 0xFFFFFFFF < 0.5 for k in keys]
    got = bloom_false_positives(keys, bloom_seed_for_uid(uid), 0.5)
    assert got.tolist() == want


def test_memtable_get_batch_matches_scalar():
    from repro.core.memtable import Memtable
    mt = Memtable(capacity_bytes=10_000, kv_size=100)
    mt.put_batch(np.array([5, 3, 5, 9]), np.array([1, 2, 3, 4]))
    out = mt.get_batch(np.array([5, 3, 4, 9], np.int64))
    assert out.tolist() == [3, 2, -1, 4]
    mt.put_batch(np.array([4]), np.array([5]))   # cache must invalidate
    assert mt.get_batch(np.array([4], np.int64)).tolist() == [5]
