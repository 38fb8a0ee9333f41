"""Merge-path sorted-run merge as a Pallas TPU kernel.

The paper's compaction hot loop is a sequential two-pointer merge — a shape
that wastes a TPU.  The TPU-native formulation used here:

* the output is tiled into blocks of 1024 keys, one ``(8, 128)`` int32
  vreg per plane;
* the **merge-path diagonal** of every output block — how many elements of
  run A precede it — is found up front by one vectorized binary search
  over all blocks (plain XLA gathers, :func:`_diagonals`) and handed to the
  kernel by scalar prefetch;
* each grid step pulls the two aligned ``(8, 128)`` blocks of each run
  that hold its 1024-key window (the block index maps read the prefetched
  diagonals, so the pipeline DMAs them from HBM) and rotates each window
  out of its 2048-key span with rolls and selects;
* the step's outputs are the 1024 smallest keys of the two windows.  With
  B's window reversed (a lane and a sublane gather), ``A ++ reversed(B)``
  is bitonic, so one elementwise min of ``A[i]`` and ``B[1023 - i]``
  leaves exactly those 1024 as a bitonic sequence, and ten half-cleaner
  stages (strides 512 down to 1: sublane rolls, then lane rolls, each a
  compare and a select) sort it.

That is O(log 1024) vector work per key, with no transposes, no reductions
and one full block written per plane per step.  Every ref access is whole
``(8, 128)`` blocks, which is what Mosaic can prove aligned.  Only the
blocks a step needs are in VMEM, so run length is bounded by HBM.

Keys are int64 split into (hi, lo) int32 planes (TPU int64 arithmetic is
emulated and slow; 2×int32 lexicographic compares are native).  Payload
seqnos ride along as a third int32 plane.  A network is not stable by
itself, so every candidate carries a tie tag: A's window index ``i``, or
``1024 + j`` for B's ``j``; keys compare as (hi, lo, tag), which is the
merge's own order — A first on equal keys, whatever the seqnos, and each
run's duplicates in their input order.  Feeding runs oldest-first thus
keeps duplicate keys seq-ascending.

Layout contract (enforced by ops.py): a run is packed as ``[3, G, 8,
128]`` (planes hi, lo, seq), padded with +inf sentinels to ``G - 1`` whole
blocks **plus one extra block of sentinels**, so every window is in bounds
and "run exhausted" needs no special casing (keys must lie below the
sentinel, int64 max).  The output is ``[3, T, 8, 128]``: the merged planes
in order, ``T`` blocks of 1024.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128                # VPU lane width
SUB = 8                    # sublanes of an int32 vreg
BLOCK = SUB * LANES        # keys per (8, 128) block; one output block a step
PLANES = 3                 # hi, lo, seq
SENTINEL = jnp.iinfo(jnp.int32).max


def _lex_le(a_hi, a_lo, b_hi, b_lo):
    """(a_hi, a_lo) <= (b_hi, b_lo) lexicographic; lo planes are pre-biased
    (xor 0x80000000) so signed int32 compare == unsigned compare on raw."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _diagonals(a, b, n_tiles: int):
    """``a0[t]`` = number of A elements among the first ``t * BLOCK``
    merged outputs, for t in [0, n_tiles): the largest a0 with
    A[a0-1] <= B[k0-a0], by a binary search run for every block at once."""
    n_a = (a.shape[1] - 1) * BLOCK
    n_b = (b.shape[1] - 1) * BLOCK
    a_hi, a_lo = a[0].reshape(-1), a[1].reshape(-1)
    b_hi, b_lo = b[0].reshape(-1), b[1].reshape(-1)
    k0 = jnp.arange(n_tiles, dtype=jnp.int32) * BLOCK
    lo = jnp.maximum(0, k0 - n_b)
    hi = jnp.minimum(k0, n_a)

    def step(_, st):
        lo, hi = st
        mid = (lo + hi + 1) // 2
        ia = jnp.maximum(mid - 1, 0)
        ib = k0 - mid                     # == n_b lands on the sentinel block
        ok = (mid == 0) | _lex_le(a_hi[ia], a_lo[ia], b_hi[ib], b_lo[ib])
        active = lo < hi
        return (jnp.where(active & ok, mid, lo),
                jnp.where(active & ~ok, mid - 1, hi))

    lo, _ = jax.lax.fori_loop(0, n_a.bit_length() + 1, step, (lo, hi))
    return lo


def _iota(axis):
    return jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), axis)


def _window(blk0, blk1, start):
    """Elements ``[start, start + BLOCK)`` of a run, one ``(8, 128)``
    block per plane, out of the aligned block ``start // BLOCK`` and the
    one after: a rotation of the 2048-key span by ``start % BLOCK``."""
    s = start % BLOCK
    q, r = s // LANES, s % LANES
    row, lane = _iota(0), _iota(1)
    up_q = (SUB - q) % SUB             # roll by up_q: out[j] = in[j + q]
    up_r = (LANES - r) % LANES
    out = []
    for p in range(PLANES):
        v0 = pltpu.roll(blk0[p, 0], up_q, 0)
        v1 = pltpu.roll(blk1[p, 0], up_q, 0)
        rows0 = jnp.where(row + q < SUB, v0, v1)            # span row q + j
        rows1 = jnp.where(row + q + 1 < SUB, pltpu.roll(v0, SUB - 1, 0),
                          pltpu.roll(v1, SUB - 1, 0))       # span row q + j + 1
        out.append(jnp.where(lane + r < LANES, pltpu.roll(rows0, up_r, 1),
                             pltpu.roll(rows1, up_r, 1)))
    return out


def _partner(v, stride, low):
    """``v[i ^ stride]`` in flat order: ``v[i + stride]`` where ``low``
    (bit ``stride`` of i clear), else ``v[i - stride]``."""
    axis, k, n = (0, stride // LANES, SUB) if stride >= LANES else \
        (1, stride, LANES)
    return jnp.where(low, pltpu.roll(v, n - k, axis), pltpu.roll(v, k, axis))


def _reverse(v):
    """``v[1023 - i]`` in flat order: one lane and one sublane gather (ten
    roll stages would serialize the step on the rolls' latency)."""
    v = jnp.take_along_axis(v, (LANES - 1) - _iota(1), axis=1,
                            mode="promise_in_bounds")
    return jnp.take_along_axis(v, (SUB - 1) - _iota(0), axis=0,
                               mode="promise_in_bounds")


def _before(x, y):
    """x precedes y in the merge's order: (hi, lo, tie tag) lexicographic."""
    hi, lo, tag = 0, 1, 3
    return (x[hi] < y[hi]) | ((x[hi] == y[hi]) & (
        (x[lo] < y[lo]) | ((x[lo] == y[lo]) & (x[tag] < y[tag]))))


_STRIDES = tuple(1 << e for e in reversed(range(BLOCK.bit_length() - 1)))


def _merge_kernel(diag_ref, a0_ref, a1_ref, b0_ref, b1_ref, out_ref):
    t = pl.program_id(0)
    a_start = diag_ref[t]
    b_start = t * BLOCK - a_start
    idx = _iota(0) * LANES + _iota(1)          # flat index in the block
    low = [(idx & s) == 0 for s in _STRIDES]

    a = _window(a0_ref, a1_ref, a_start) + [idx]
    b = [_reverse(v) for v in _window(b0_ref, b1_ref, b_start)]
    b.append(2 * BLOCK - 1 - idx)              # B's tag 1024 + j, reversed

    # min(A[i], B[1023 - i]): the step's 1024 outputs, a bitonic sequence
    take_a = _before(a, b)
    x = [jnp.where(take_a, va, vb) for va, vb in zip(a, b)]
    for s, lo_s in zip(_STRIDES, low):         # half-cleaners: sort it
        y = [_partner(v, s, lo_s) for v in x]
        keep = _before(x, y) == lo_s           # min to the low index
        x = [jnp.where(keep, vx, vy) for vx, vy in zip(x, y)]
    for p in range(PLANES):
        out_ref[p, 0] = x[p]


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_path_call(a, b, *, interpret: bool):
    """Stable merge of two packed runs (see the layout contract above).

    ``a``: ``[3, G_a, 8, 128]`` int32, ``b``: ``[3, G_b, 8, 128]``; the
    runs hold ``n_a = (G_a - 1) * BLOCK`` and ``n_b`` elements, sentinels
    included.  Returns the merged planes as ``[3, T, 8, 128]`` int32,
    ``T = (n_a + n_b) / BLOCK`` (real elements first, then sentinels).
    """
    assert a.shape[0] == PLANES and a.shape[2:] == (SUB, LANES)
    assert b.shape[0] == PLANES and b.shape[2:] == (SUB, LANES)
    g_a, g_b = a.shape[1], b.shape[1]
    n_tiles = g_a + g_b - 2
    diag = _diagonals(a, b, n_tiles)

    def a_block(off):
        return lambda t, d: (0, jnp.minimum(d[t] // BLOCK + off, g_a - 1),
                             0, 0)

    def b_block(off):
        return lambda t, d: (0, jnp.minimum((t * BLOCK - d[t]) // BLOCK
                                            + off, g_b - 1), 0, 0)

    blk = (PLANES, 1, SUB, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(blk, index) for index in
                  (a_block(0), a_block(1), b_block(0), b_block(1))],
        out_specs=pl.BlockSpec(blk, lambda t, d: (0, t, 0, 0)),
    )
    return pl.pallas_call(
        _merge_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((PLANES, n_tiles, SUB, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(diag, a, a, b, b)
