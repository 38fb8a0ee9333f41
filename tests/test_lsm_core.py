"""Unit + property tests for the vLSM core (the paper's data structures)."""

import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:      # hermetic env: deterministic mini-shim
    from _propshim import HealthCheck, given, settings, st

from repro.core import LSMConfig, LSMTree, Policy, Simulator, DeviceModel
from repro.core import merge as merge_backend
from repro.core.memtable import Memtable
from repro.core.sst import SST, overlapping, split_fixed
from repro.core.vsst import (l2_fences, overlap_count_range, plan_vssts,
                             select_good_vssts)

CFG = LSMConfig.vlsm_default(scale=1 << 16)  # tiny: fast trees in tests


# --------------------------------------------------------------- memtable
def test_memtable_latest_wins():
    mt = Memtable(capacity_bytes=10_000, kv_size=100)
    mt.put_batch(np.array([5, 3, 5]), np.array([1, 2, 3]))
    keys, seqs = mt.to_sorted()
    assert keys.tolist() == [3, 5]
    assert seqs.tolist() == [2, 3]
    assert mt.get(5) == 3
    assert mt.get(99) is None


# -------------------------------------------------------------------- SST
def test_overlapping_selection():
    ssts = [SST(np.arange(i * 10, i * 10 + 10, dtype=np.int64),
                np.zeros(10, np.int64), 100) for i in range(5)]
    got = overlapping(ssts, 12, 33)
    assert [s.smallest for s in got] == [10, 20, 30]
    assert overlapping(ssts, 200, 300) == []
    assert [s.smallest for s in overlapping(ssts, -5, 0)] == [0]


def test_split_fixed_sizes():
    keys = np.arange(1000, dtype=np.int64)
    out = split_fixed(keys, keys.copy(), kv_size=100, sst_size=10_000)
    assert all(s.size <= 10_000 for s in out)
    assert sum(s.n for s in out) == 1000


# ------------------------------------------------------------------ merge
@given(st.lists(st.integers(0, 2**40), min_size=0, max_size=300),
       st.lists(st.integers(0, 2**40), min_size=0, max_size=300))
@settings(max_examples=30, deadline=None)
def test_merge_numpy_latest_wins(a, b):
    a = np.unique(np.asarray(a, np.int64))
    b = np.unique(np.asarray(b, np.int64))
    runs = [(b, np.arange(1000, 1000 + b.size)),   # newer
            (a, np.arange(a.size))]                 # older
    keys, seqs = merge_backend.merge_runs(runs)
    assert np.all(np.diff(keys) > 0)
    ref = {}
    for k, s in zip(a.tolist(), range(a.size)):
        ref[k] = s
    for k, s in zip(b.tolist(), range(1000, 1000 + b.size)):
        ref[k] = s
    assert dict(zip(keys.tolist(), seqs.tolist())) == ref


# ---------------------------------------------------------------- vSSTs
def _mk_l2(n_ssts, keys_per, kv=100, spacing=1000):
    out = []
    for i in range(n_ssts):
        ks = np.arange(i * spacing, i * spacing + keys_per, dtype=np.int64)
        out.append(SST(ks, np.zeros(keys_per, np.int64), kv))
    return out


def test_overlap_count():
    l2 = _mk_l2(10, 100)
    lo, hi = l2_fences(l2)
    assert overlap_count_range(lo, hi, 0, 50) == 1
    assert overlap_count_range(lo, hi, 0, 1000) == 2
    assert overlap_count_range(lo, hi, 150, 150) == 0   # in a gap
    assert overlap_count_range(lo, hi, -10, 10**9) == 10


@given(st.integers(2, 40), st.integers(0, 2**20))
@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_plan_vssts_properties(n_l2, seed):
    """Plans must tile the stream exactly; sizes within [S_m, S_M] except a
    possibly-bigger merged tail; good plans have overlap <= f."""
    rng = np.random.default_rng(seed)
    kv, f = 100, 4
    s_M, s_m = 40 * kv, 10 * kv
    l2 = _mk_l2(n_l2, 50, kv=kv, spacing=5000)
    lo, hi = l2_fences(l2)
    keys = np.unique(rng.integers(0, n_l2 * 5000, size=600).astype(np.int64))
    plans = plan_vssts(keys, kv, s_m, s_M, f, lo, hi, sst_size_l2=50 * kv)
    assert plans[0].start == 0 and plans[-1].end == keys.size
    for a, b in zip(plans, plans[1:]):
        assert a.end == b.start
    for p in plans:
        n = p.end - p.start
        assert n * kv <= s_M + s_m + kv   # S_M + tail-absorption slack
        got = overlap_count_range(lo, hi, int(keys[p.start]),
                                  int(keys[p.end - 1]))
        assert got == p.overlap_ssts
        if p.good:
            assert p.overlap_ssts <= f


@given(st.integers(1, 40), st.integers(0, 2**20), st.integers(1, 6),
       st.integers(2, 60))
@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_plan_vssts_matches_ref(n_l2, seed, f, max_kv):
    """The closed-form planner is plan-for-plan identical to the segment
    walk across fence densities, growth factors and size windows."""
    from repro.core.vsst import plan_vssts_ref
    rng = np.random.default_rng(seed)
    kv = 100
    s_M, s_m = max_kv * kv, max(1, max_kv // 4) * kv
    l2 = _mk_l2(n_l2, 50, kv=kv, spacing=int(rng.integers(100, 5000)))
    lo, hi = l2_fences(l2)
    keys = np.unique(rng.integers(-500, n_l2 * 5000,
                                  size=int(rng.integers(1, 500))
                                  ).astype(np.int64))
    args = (keys, kv, s_m, s_M, f, lo, hi, 50 * kv)
    assert plan_vssts(*args) == plan_vssts_ref(*args)
    # empty-fence degenerate case
    z = np.empty(0, np.int64)
    args = (keys, kv, s_m, s_M, f, z, z, 50 * kv)
    assert plan_vssts(*args) == plan_vssts_ref(*args)


def test_select_good_prefers_low_ratio():
    kv, f = 100, 4
    l2 = _mk_l2(8, 50, kv=kv, spacing=5000)
    lo, hi = l2_fences(l2)
    # one vSST inside a single L2 SST (good, low ratio), one spanning many
    good = SST(np.arange(0, 40, dtype=np.int64), np.zeros(40, np.int64), kv)
    poor = SST(np.arange(100, 40_000, 800, dtype=np.int64),
               np.zeros(50, np.int64), kv)
    picked = select_good_vssts([poor, good], lo, hi, 50 * kv, f,
                               bytes_needed=1)
    assert picked == [1]


# ------------------------------------------------------------- tree props
@given(st.integers(0, 2**32), st.integers(200, 3000))
@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_tree_get_after_put_latest_wins(seed, n_ops):
    rng = np.random.default_rng(seed)
    for policy_cfg in (CFG, LSMConfig.rocksdb_default(scale=1 << 16)):
        sim = Simulator(policy_cfg, DeviceModel.scaled(1 / 1024))
        keys = rng.integers(0, 500, size=n_ops).astype(np.int64)  # duplicates!
        ops = np.zeros(n_ops, np.uint8)
        arr = np.arange(n_ops) / 1e4
        sim.run(ops, keys, arr)
        tree = sim.trees[0]
        tree.check_invariants()
        view = tree.merged_view()
        # latest-wins: last occurrence of key in stream has highest seq
        last_seq = {}
        for i, k in enumerate(keys.tolist()):
            last_seq[k] = i
        assert view == last_seq
        # point lookups agree with the merged view on a sample
        for k in list(view)[:50]:
            got, _r, _p = tree.get(k)
            assert got == view[k]
        missing, _r, _p = tree.get(10**15)
        assert missing is None


def test_vlsm_level_structure():
    sim = Simulator(CFG, DeviceModel.scaled(1 / 1024))
    rng = np.random.default_rng(0)
    n = 5000
    sim.run(np.zeros(n, np.uint8),
            rng.integers(0, 2**40, n).astype(np.int64),
            np.arange(n) / 1e4)
    tree = sim.trees[0]
    tree.check_invariants()
    st_ = sim.stats
    assert st_.vssts_good + st_.vssts_poor > 0
    # the paper's Φ=32 regime: most vSSTs are good (Fig 13b shows ~90%)
    frac_good = st_.vssts_good / (st_.vssts_good + st_.vssts_poor)
    assert frac_good > 0.5
    # L0 never exceeds the stop limit structurally
    assert len(tree.levels[0]) <= CFG.l0_stop_ssts


def test_merge_backends_agree():
    rng = np.random.default_rng(3)
    a = np.unique(rng.integers(0, 2**40, 400).astype(np.int64))
    b = np.unique(rng.integers(0, 2**40, 300).astype(np.int64))
    runs = [(b, np.arange(500, 500 + b.size)), (a, np.arange(a.size))]
    merge_backend.set_backend("numpy")
    k1, s1 = merge_backend.merge_runs(runs)
    try:
        merge_backend.set_backend("jnp")
        k2, s2 = merge_backend.merge_runs(runs)
        merge_backend.set_backend("pallas")
        k3, s3 = merge_backend.merge_runs(runs)
    finally:
        merge_backend.set_backend("numpy")
    assert np.array_equal(k1, k2) and np.array_equal(s1, s2)
    assert np.array_equal(k1, k3) and np.array_equal(s1, s3)
