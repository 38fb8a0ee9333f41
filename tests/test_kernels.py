"""Per-kernel allclose vs pure-jnp oracles, shape/dtype sweeps (kernel
bodies run in the Pallas interpreter on the CPU; TPU is the target —
tests/test_tpu_compile.py compiles the store's three for it)."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


# ------------------------------------------------------------- merge_path
def _merge_case(kind, n, m, rng):
    """Two sorted runs with seqnos.  ``random``: wide keys, two shared;
    ``ties``: few distinct keys, so equal keys meet across and inside the
    runs, B's seqnos *lower* than A's; ``equal``: every key the same."""
    if kind == "random":
        a = np.sort(rng.integers(-2**46, 2**46, n).astype(np.int64))
        b = np.sort(rng.integers(-2**46, 2**46, m).astype(np.int64))
        if n > 2 and m > 2:
            b[:2] = a[:2]
            b = np.sort(b)
        return a, np.arange(n), b, np.arange(n, n + m)
    if kind == "ties":
        vals = rng.integers(-2**40, 2**40, max(2, (n + m) // 300))
        a = np.sort(rng.choice(vals, n))
        b = np.sort(rng.choice(vals, m))
        return a, np.arange(m, m + n), b, np.arange(m)
    assert kind == "equal"
    a, b = np.full(n, 7 << 33, np.int64), np.full(m, 7 << 33, np.int64)
    return a, np.arange(m, m + n)[::-1], b, np.arange(m)


_SIZES = (1, 1023, 1024, 1025, 2047, 2048, 41_943)


@pytest.mark.parametrize("kind,n,m", [
    pytest.param("random", n, m, id=f"{n}-{m}")
    for n, m in [(1, 1), (7, 130), (128, 128), (257, 511), (1000, 2500)]
] + [
    pytest.param("ties", n, m, id=f"ties-{n}-{m}")
    for n, m in zip(_SIZES, _SIZES[::-1] + (1024,))
] + [
    pytest.param("ties", n, n, id=f"ties-{n}-{n}")
    for n in _SIZES[1:] if n != 1025
] + [
    pytest.param("equal", 1025, 2047, id="equal-1025-2047"),
    pytest.param("equal", 41_943, 1, id="equal-41943-1"),
    # the RocksDB shape: a wide accumulated run against one small run
    pytest.param("ties", 1_500_000, 41_943, id="ties-1500000-41943"),
])
def test_merge_path(kind, n, m):
    """The kernel is an exact stable merge against ``np.argsort(kind=
    "stable")``: A first on equal keys whatever the seqnos, duplicates
    inside a run in their order, windows that straddle block and bucket
    edges and land on the sentinel block."""
    from repro.kernels.merge_path import ops
    a, asq, b, bsq = _merge_case(kind, n, m, np.random.default_rng(n * 1000 + m))
    k, s = ops.merge_two_runs_np(a, asq, b, bsq)
    kk = np.concatenate([a, b]); ss = np.concatenate([asq, bsq])
    order = np.argsort(kk, kind="stable")
    assert np.array_equal(k, kk[order])
    assert np.array_equal(s, ss[order])


def test_merge_path_planes_roundtrip():
    from repro.kernels.merge_path.ops import join_planes, split_planes
    rng = np.random.default_rng(0)
    keys = rng.integers(-2**62, 2**62, 1000).astype(np.int64)
    hi, lo = split_planes(keys)
    assert np.array_equal(join_planes(hi, lo), keys)
    # order preservation under (hi, lo) lexicographic compare
    order = np.lexsort((lo.astype(np.int64), hi.astype(np.int64)))
    assert np.array_equal(keys[order], np.sort(keys))


def test_merge_path_compiles_once_per_bucket():
    """Run lengths pad to power-of-two buckets: merges of many lengths
    inside one bucket pair share one compiled kernel shape."""
    from repro.kernels.merge_path import merge_path_call, ops
    rng = np.random.default_rng(11)
    before = merge_path_call._cache_size()
    for n, m in [(1100, 1500), (1300, 2000), (2047, 1025), (1999, 1800)]:
        a = np.sort(rng.choice(2**40, n, replace=False)).astype(np.int64)
        b = np.sort(rng.choice(2**40, m, replace=False)).astype(np.int64)
        k, _ = ops.merge_two_runs_np(a, np.arange(n), b, np.arange(m))
        assert np.array_equal(k, np.sort(np.concatenate([a, b]),
                                          kind="stable"))
    assert merge_path_call._cache_size() - before <= 1
    from repro.kernels.platform import bucket
    assert bucket(1025, 1024) == 2048 == bucket(2048, 1024)


def test_pallas_merge_folds_disjoint_runs():
    """A compaction's inputs — disjoint SSTs of two sorted levels plus an
    overlapping L0 run — merge on the kernel exactly like numpy, with
    latest-wins dedup across the folded groups."""
    from repro.core import merge as merge_backend
    rng = np.random.default_rng(12)
    keys = np.sort(rng.choice(2**40, 6000, replace=False)).astype(np.int64)
    lower = [keys[i:i + 1000] for i in range(0, 6000, 1000)]   # "L2" SSTs
    upper = [keys[500:2500:2], keys[3000:5000:3]]              # "L1" SSTs
    l0 = np.sort(rng.choice(keys, 700, replace=False))
    seq = iter(range(10**6))
    def run(k):
        return k, np.fromiter((next(seq) for _ in k), np.int64, k.shape[0])
    runs = [run(k) for k in lower] + [run(k) for k in upper] + [run(l0)]
    runs = runs[::-1]                                          # newest first
    groups = merge_backend._disjoint_groups(runs[::-1])
    assert len(groups) == 3
    want = merge_backend._merge_numpy(runs)
    merge_backend.set_backend("pallas")
    try:
        got = merge_backend.merge_runs(runs)
    finally:
        merge_backend.set_backend("numpy")
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


# ------------------------------------------------------------ overlap_scan
@pytest.mark.parametrize("nf,nk", [(1, 5), (130, 7), (640, 1000)])
def test_overlap_scan(nf, nk):
    from repro.kernels.overlap_scan import ops
    rng = np.random.default_rng(nf + nk)
    f = np.sort(rng.integers(-2**45, 2**45, nf).astype(np.int64))
    k = rng.integers(-2**45, 2**45, nk).astype(np.int64)
    k[: min(nf, nk) // 2] = f[: min(nf, nk) // 2]
    got = ops.fence_rank_np(f, k)
    assert np.array_equal(got, np.searchsorted(f, k, side="right"))


# ------------------------------------------------------------ lindley_scan
@pytest.mark.parametrize("n,rho,d0", [
    (1, 0.5, None), (7, 0.9, None), (128, 1.1, None), (257, 0.8, 3.0),
    (1000, 1.05, None), (513, 0.0, 12.5),
])
def test_lindley_scan(n, rho, d0):
    """All three backends vs the monolithic numpy recursion (the DES's
    own accounting pass), across under/over-saturated queues and
    carried-in clocks.  Tolerance is f64 roundoff of the blocked
    cumsum."""
    from repro.kernels.lindley_scan import ops
    rng = np.random.default_rng(n + int(rho * 10))
    service = rng.exponential(1e-6, n) if rho > 0 else np.zeros(n)
    mean_s = max(service.mean(), 1e-12)
    arrivals = np.cumsum(rng.exponential(mean_s / max(rho, 1e-3), n))
    arrivals += 100.0          # DES-scale absolute times vs us latencies
    want = ops.lindley_numpy(service, arrivals,
                             d0=d0 if d0 is not None else float("-inf"))
    for backend in ("jnp", "pallas", "numpy"):
        got = ops.lindley_np(service, arrivals,
                             d0=d0 if d0 is not None else float("-inf"),
                             backend=backend)
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    # departures are monotone and never precede arrival + service
    assert np.all(np.diff(want) >= -1e-15)
    assert np.all(want >= arrivals + service - 1e-9)


@pytest.mark.parametrize("kind", ["constant", "exponential"])
def test_lindley_scan_within_stated_tolerance(kind):
    """A long saturated queue at DES-scale absolute times: the double-f32
    kernel stays within ``departure_tolerance`` of the numpy recursion,
    and its own error against an extended-precision recursion stays
    inside the kernel's share of that bound."""
    from repro.kernels.lindley_scan import departure_tolerance, ops
    from repro.kernels.lindley_scan.kernel import BLOCK
    n = 40_000
    rng = np.random.default_rng(3)
    service = np.full(n, 1.7e-5) if kind == "constant" \
        else rng.exponential(1.7e-5, n)
    arrivals = 90.0 + np.arange(n) * 1e-6     # flood: one busy period
    want = ops.lindley_numpy(service, arrivals)
    got = ops.lindley_np(service, arrivals, backend="pallas")
    scale = float(np.max(np.abs(want)))
    assert np.max(np.abs(got - want)) <= departure_tolerance(n, scale)
    s = np.cumsum(service.astype(np.longdouble))
    base = arrivals.astype(np.longdouble)
    base[1:] -= s[:-1]
    exact = s + np.maximum.accumulate(base)
    kernel_share = (n / BLOCK + 16) * 2.0**-46 * scale
    assert float(np.max(np.abs(got - exact))) <= kernel_share


def test_lindley_scan_batched_ragged():
    from repro.kernels.lindley_scan import ops
    rng = np.random.default_rng(0)
    lens = [0, 1, 130, 512, 77]
    services = [rng.exponential(2e-6, L) for L in lens]
    arrivals = [np.cumsum(rng.exponential(1.5e-6, L)) + 50.0 for L in lens]
    d0 = [float("-inf"), 50.0, float("-inf"), 51.0, float("-inf")]
    for backend in ("pallas", "jnp", "numpy"):
        got = ops.lindley_batch_np(services, arrivals, d0, backend=backend)
        assert len(got) == len(lens)
        for g, s, a, c in zip(got, services, arrivals, d0):
            np.testing.assert_allclose(g, ops.lindley_numpy(s, a, c),
                                       rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------- platform
def test_interpret_mode_follows_the_platform():
    from repro.kernels.platform import interpret_mode
    assert interpret_mode() is (jax.default_backend() != "tpu")


def test_compile_cache_directory(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the fixed
    checkout-root directory, never a temp or per-process name."""
    from repro.kernels import platform
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert platform.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before[0]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = platform.enable_compile_cache()
        assert path == str(platform.CACHE_DIR)
        assert platform.CACHE_DIR.name == ".jax_cache"
        assert (platform.CACHE_DIR.parent / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
