"""Numpy entry points for lindley_scan: padding, ragged batch, the
double-f32 split for the kernel and the x64 scope for the jnp oracle.

The DES hands over ragged per-queue (service, arrivals) arrays — one row
per shard, or per (policy, config, shard) point of a whole sweep matrix.
``lindley_batch_np`` pads them into ONE [B, N] program per length bucket
(pallas blocked scan, or the vmapped jnp oracle) and slices the
departures back out; the fleet engine's final latency accounting is
exactly one such call.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from repro import obs

from ..platform import bucket, interpret_mode
from .kernel import BLOCK, LANES, NEG, lindley_scan_call

_NEG_INF = float("-inf")

# Pad-plan cache, keyed by the batch's length tuple.  A load curve (and
# every executor cache hit) evaluates the same queue SHAPES at each grid
# point — arrivals change, lengths do not — so the power-of-two bucket
# map and the padded (S, A) buffers are reused across calls instead of
# being rebuilt/refilled every factor.  The pad regions' fill (0 service
# / -inf arrival) is shape-invariant, so reused buffers only need their
# real-data prefixes rewritten; results are byte-identical to a fresh
# allocation.  Bounded LRU: entries hold [b, n_pad] float64 buffers.
_plan_cache: OrderedDict = OrderedDict()
_PLAN_CACHE_MAX = 32
# numpy-tier scratch (c_buf, g_buf), grown monotonically: shared across
# calls for the same first-touch-avoidance reason.
_np_scratch: list[np.ndarray] = [np.empty(0, np.float64),
                                 np.empty(0, np.float64)]


def _pad_plan(lens: tuple[int, ...]) -> list[tuple]:
    """The cached padding plan for one batch shape: a list of
    ``(n_pad, idxs, S, A)`` per occupied power-of-two bucket."""
    plan = _plan_cache.get(lens)
    if plan is not None:
        _plan_cache.move_to_end(lens)
        return plan
    buckets: dict[int, list[int]] = {}
    for i, ln in enumerate(lens):
        if ln == 0:
            continue
        buckets.setdefault(bucket(ln, BLOCK), []).append(i)
    plan = []
    for n_pad, idxs in sorted(buckets.items()):
        S = np.zeros((len(idxs), n_pad), np.float64)
        # -inf arrival padding: the padded G terms never win the running
        # max, so real departures are unaffected and pad outputs are
        # sliced away.
        A = np.full((len(idxs), n_pad), _NEG_INF, np.float64)
        plan.append((n_pad, idxs, S, A))
    _plan_cache[lens] = plan
    while len(_plan_cache) > _PLAN_CACHE_MAX:
        _plan_cache.popitem(last=False)
    return plan


def clear_pad_plans() -> None:
    """Drop the cached pad plans and numpy scratch (tests / memory)."""
    _plan_cache.clear()
    _np_scratch[0] = np.empty(0, np.float64)
    _np_scratch[1] = np.empty(0, np.float64)


@obs.traced("lindley.batch")
def lindley_batch_np(services: list[np.ndarray], arrivals: list[np.ndarray],
                     d0: list[float] | None = None,
                     backend: str = "pallas") -> list[np.ndarray]:
    """Departure times for a ragged batch of FIFO queues.

    ``services[i]``/``arrivals[i]`` are queue i's per-op service times and
    arrival times (1-D, equal length, possibly empty); ``d0[i]`` the
    carried-in departure clock (default -inf: fresh queue).  Returns the
    per-queue departure arrays.  ``backend``: "pallas" (blocked-scan
    kernel in double-f32, interpreted off the TPU; within
    ``departure_tolerance`` of the numpy recursion), "jnp" (vmapped float64
    oracle, CPU only), or "numpy"
    (:func:`lindley_numpy` per queue — no padding, no device: XLA's CPU
    lowering serializes cumulative scans at ~20x numpy's throughput and
    the padded batch costs ~2x extra memory traffic, so this is the
    CPU-tier choice for large sweep matrices; the kernel tests assert
    that all three agree).

    Very ragged batches (a sweep mixing 1-shard and 16-shard queues) are
    padded in power-of-two length *buckets* rather than to the single
    global max: one device program per occupied bucket, each [b_i, n_i]
    with <2x pad waste, instead of one [B, n_max] program that would
    inflate every short queue to the longest.
    """
    assert backend in ("pallas", "jnp", "numpy")
    b = len(services)
    assert len(arrivals) == b
    if d0 is None:
        d0 = [_NEG_INF] * b
    lens = [int(s.shape[0]) for s in services]
    if max(lens, default=0) == 0:
        return [np.empty(0, np.float64) for _ in range(b)]
    if backend == "numpy":
        # lindley_numpy per queue, but with two scratch buffers shared
        # across the batch AND across calls (module scratch, grown
        # monotonically): fresh first-touch allocations dominate the
        # plain per-queue loop on big matrices, and only the departure
        # array escapes.  Operation order matches lindley_numpy exactly
        # (bit-identical results — the parity anchor).
        nmax = max(lens)
        if _np_scratch[0].shape[0] < nmax:
            _np_scratch[0] = np.empty(nmax, np.float64)
            _np_scratch[1] = np.empty(nmax, np.float64)
        c_buf, g_buf = _np_scratch
        outs = []
        for s, a, d, ln in zip(services, arrivals, d0, lens):
            if ln == 0:
                outs.append(np.empty(0, np.float64))
                continue
            cc, gg = c_buf[:ln], g_buf[:ln]
            np.cumsum(np.asarray(s, np.float64), out=cc)
            np.copyto(gg, a)
            gg[1:] -= cc[:-1]
            np.maximum(gg, d, out=gg)
            np.maximum.accumulate(gg, out=gg)
            outs.append(cc + gg)
        return outs
    # bucket i by padded length: BLOCK * 2^ceil(log2(len/BLOCK)) — the
    # plan (bucket map + padded buffers) is cached across calls
    obs.count("lindley.ops", sum(lens))
    out: list[np.ndarray | None] = [np.empty(0, np.float64)] * b
    for n_pad, idxs, S, A in _pad_plan(tuple(lens)):
        with obs.span("lindley.fill"):
            for row, i in enumerate(idxs):
                S[row, :lens[i]] = services[i]
                A[row, :lens[i]] = arrivals[i]
            D0 = np.asarray([d0[i] for i in idxs], np.float64)
        dep = _pallas(S, A, D0) if backend == "pallas" else _jnp(S, A, D0)
        obs.count("lindley.padded_ops", S.size)
        for row, i in enumerate(idxs):
            out[i] = dep[row, :lens[i]]
    return out


def _pairs(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """float64 -> double-f32 (hi, lo) with hi + lo == x to ~2^-48."""
    x = np.maximum(x, NEG)          # -inf padding / fresh clock -> NEG
    hi = x.astype(np.float32)
    return hi, (x - hi).astype(np.float32)


def _pallas(S: np.ndarray, A: np.ndarray, D0: np.ndarray) -> np.ndarray:
    b, n = S.shape
    with obs.span("lindley.split"):
        planes = [p.reshape(b, n // LANES, LANES)
                  for p in (*_pairs(S), *_pairs(A))]
        d0 = np.stack(_pairs(D0), axis=1).reshape(-1)
    with obs.span("lindley.call"):
        out = lindley_scan_call(d0, *planes, interpret=interpret_mode())
        hi, lo = (np.asarray(x, np.float64) for x in out)
    if obs.enabled():
        obs.count("lindley.h2d_bytes",
                  d0.nbytes + sum(p.nbytes for p in planes))
        obs.count("lindley.d2h_bytes", sum(x.nbytes for x in out))
    return (hi + lo).reshape(b, n)


def _jnp(S: np.ndarray, A: np.ndarray, D0: np.ndarray) -> np.ndarray:
    import jax
    from .ref import lindley_ref_batch
    with jax.enable_x64(True):
        return np.asarray(lindley_ref_batch(S, A, D0), np.float64)


def lindley_np(service: np.ndarray, arrivals: np.ndarray,
               d0: float = _NEG_INF, backend: str = "pallas") -> np.ndarray:
    """Single-queue convenience wrapper over :func:`lindley_batch_np`."""
    return lindley_batch_np([np.asarray(service, np.float64)],
                            [np.asarray(arrivals, np.float64)],
                            [d0], backend=backend)[0]


def lindley_numpy(service: np.ndarray, arrivals: np.ndarray,
                  d0: float = _NEG_INF) -> np.ndarray:
    """The monolithic numpy recursion — bit-identical to the DES's
    per-shard accounting pass in ``Simulator.run`` (the parity anchor the
    kernel tests compare both backends against)."""
    s = np.asarray(service, np.float64)
    a = np.asarray(arrivals, np.float64)
    if s.shape[0] == 0:
        return np.empty(0, np.float64)
    s_cum = np.cumsum(s)
    base = a.copy()
    base[1:] -= s_cum[:-1]
    return s_cum + np.maximum.accumulate(np.maximum(base, d0))
