"""Blocked Lindley (max-plus) scan as a Pallas TPU kernel.

The recursion D_j = S_j + max(d0, max_{k<=j}(a_k - S_{k-1})) decomposes
over fixed-size blocks exactly like any prefix scan: a block computes its
local inclusive cumsum and running max, then folds in two carries from
the blocks before it — the accumulated service sum ``s_off`` and the
running max-plus state ``m``.  Both carries live in VMEM scratch across
the minor grid dimension, initialised at block 0 from the row's ``d0``
(read from SMEM).

Grid: (B rows, N / 1024); a row is laid out as ``[N / 128, 128]`` and a
block is one ``(8, 128)`` tile, scanned along lanes (Hillis–Steele over
``pltpu.roll`` shifts) and then along sublanes.

Precision: absolute simulated times (~1e2 s) against microsecond
latencies leave float32 with no significant bits in the tail, and Mosaic
has no 64-bit floats.  Every value is therefore carried as a **double-f32
pair** ``(hi, lo)`` with ``hi + lo`` the float64 value: additions use
Knuth's error-free TwoSum, maxima compare lexicographically (exact).  A
pair holds 48 significand bits; one addition errs by at most ~2^-46 of
its result.  :func:`departure_tolerance` states the resulting bound
against the float64 reference ``lindley_numpy``, and the tests pin it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUB = 8
BLOCK = SUB * LANES        # elements per grid step; rows pad to a multiple
NEG = -1e30                # finite "-inf": arrival padding and a fresh d0


def departure_tolerance(n: int, scale: float) -> float:
    """Stated bound on |kernel - ``lindley_numpy``| (seconds) for a row of
    ``n`` ops whose departures reach ``scale`` seconds.

    A departure is S_j + max(...) with every max exact, so both sides err
    only through their service sums.  The reference's serial float64
    cumsum errs by at most ``n * 2^-53`` of ``scale``.  The kernel adds a
    departure's block sums in at most ``log2(1024) + 6`` double-f32 steps
    and its row carry in one step per earlier block, each within
    ``2^-46`` of ``scale``.
    """
    steps = n / BLOCK + 16
    return (n * 2.0**-53 + steps * 2.0**-46) * max(1.0, abs(scale))


def _two_sum(a, b):
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fast_two_sum(a, b):          # requires |a| >= |b|
    s = a + b
    return s, b - (s - a)


def _add(x, y):
    """Double-f32 addition (accurate variant: two TwoSums, renormalised)."""
    s, e = _two_sum(x[0], y[0])
    t, f = _two_sum(x[1], y[1])
    s, e = _fast_two_sum(s, e + t)
    return _fast_two_sum(s, e + f)


def _max(x, y):
    gt = (x[0] > y[0]) | ((x[0] == y[0]) & (x[1] > y[1]))
    return jnp.where(gt, x[0], y[0]), jnp.where(gt, x[1], y[1])


def _shift(x, fill, d: int, axis: int):
    """Each element takes the value ``d`` places before it along ``axis``;
    the first ``d`` take ``fill``."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x[0].shape, axis)
    return tuple(jnp.where(pos < d, f, pltpu.roll(v, d, axis))
                 for v, f in zip(x, fill))


def _scan(x, op, fill, axis: int):
    """Inclusive scan of a pair along ``axis`` (Hillis–Steele)."""
    d = 1
    while d < x[0].shape[axis]:
        x = op(x, _shift(x, fill, d, axis))
        d *= 2
    return x


def _last(x, axis: int):
    """The last element along ``axis``, broadcast back over it (a masked
    sum with one non-zero term is exact)."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x[0].shape, axis)
    last = x[0].shape[axis] - 1
    return tuple(jnp.broadcast_to(
        jnp.sum(jnp.where(pos == last, v, 0.0), axis=axis, keepdims=True),
        v.shape) for v in x)


def _lindley_kernel(d0_ref, s_hi_ref, s_lo_ref, a_hi_ref, a_lo_ref,
                    o_hi_ref, o_lo_ref, carry_ref):
    row, t = pl.program_id(0), pl.program_id(1)
    zero = (0.0, 0.0)
    neg = (NEG, 0.0)

    @pl.when(t == 0)
    def _init():
        carry_ref[0] = jnp.zeros((SUB, LANES), jnp.float32)
        carry_ref[1] = jnp.zeros((SUB, LANES), jnp.float32)
        carry_ref[2] = jnp.full((SUB, LANES), d0_ref[2 * row], jnp.float32)
        carry_ref[3] = jnp.full((SUB, LANES), d0_ref[2 * row + 1],
                                jnp.float32)

    s_off = (carry_ref[0], carry_ref[1])  # service sum of earlier blocks
    m_in = (carry_ref[2], carry_ref[3])   # running max-plus state
    s = (s_hi_ref[0], s_lo_ref[0])
    a = (a_hi_ref[0], a_lo_ref[0])

    # block-local inclusive / exclusive service sums, row-major
    lane_inc = _scan(s, _add, zero, 1)
    rows_before = _shift(_scan(_last(lane_inc, 1), _add, zero, 0), zero, 1, 0)
    inc = _add(rows_before, lane_inc)
    exc = _add(rows_before, _shift(lane_inc, zero, 1, 1))
    before = _add(s_off, exc)
    g = _add(a, (-before[0], -before[1]))          # a_k - S_{k-1}
    lane_max = _scan(g, _max, neg, 1)
    rows_max = _shift(_scan(_last(lane_max, 1), _max, neg, 0), neg, 1, 0)
    m_run = _max(_max(rows_max, lane_max), m_in)
    dep = _add(_add(s_off, inc), m_run)
    o_hi_ref[0] = dep[0]
    o_lo_ref[0] = dep[1]
    s_next = _add(s_off, _last(_last(inc, 1), 0))
    m_next = _last(_last(m_run, 1), 0)
    carry_ref[0], carry_ref[1] = s_next
    carry_ref[2], carry_ref[3] = m_next


@functools.partial(jax.jit, static_argnames=("interpret",))
def lindley_scan_call(d0, s_hi, s_lo, a_hi, a_lo, *, interpret: bool):
    """Departures of B FIFO queues as double-f32 pairs.

    ``s_*``/``a_*``: ``[B, N / 128, 128]`` float32 planes of service and
    arrival times (``N`` a multiple of 1024; pad rows with service 0 and
    arrival :data:`NEG`); ``d0``: ``[2 * B]`` float32, row ``i``'s
    carried-in clock as the pair ``(d0[2i], d0[2i + 1])``.  Returns the
    ``(hi, lo)`` departure planes, each ``[B, N / 128, 128]``.
    """
    b, rows, lanes = s_hi.shape
    assert lanes == LANES and rows % SUB == 0 and d0.shape == (2 * b,)
    blk = pl.BlockSpec((1, SUB, LANES), lambda i, t: (i, t, 0))
    plane = jax.ShapeDtypeStruct((b, rows, LANES), jnp.float32)
    return pl.pallas_call(
        _lindley_kernel,
        grid=(b, rows // SUB),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [blk] * 4,
        out_specs=[blk, blk],
        out_shape=[plane, plane],
        scratch_shapes=[pltpu.VMEM((4, SUB, LANES), jnp.float32)],
        interpret=interpret,
    )(d0, s_hi, s_lo, a_hi, a_lo)
