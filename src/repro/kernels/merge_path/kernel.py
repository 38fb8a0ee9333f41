"""Merge-path sorted-run merge as a Pallas TPU kernel.

The paper's compaction hot loop is a sequential two-pointer merge — a shape
that wastes a TPU.  The TPU-native formulation used here:

* the output is tiled into 128-element rows (the VPU lane width);
* the **merge-path diagonal** of every output tile — how many elements of
  run A precede that tile — is found up front by one vectorized binary
  search over all tiles (plain XLA gathers, :func:`_diagonals`) and handed
  to the kernel by scalar prefetch;
* each grid step then pulls only the two aligned ``(8, 128)`` blocks of
  each run that its diagonal window touches (the block index maps read
  the prefetched diagonals, so the pipeline DMAs them from HBM), selects
  the two 128-lane rows that hold the window with sublane masks, and
  ranks every candidate with ``[128, 128]`` comparison counts —
  rank(A_i) = i + |{j : B_j < A_i}|, rank(B_j) = j + |{i : A_i <= B_j}|
  — instead of a data-dependent loop;
* the scatter to output positions is a masked select-sum over the same
  ``[128, 128]`` tile (scatter-free, layout-friendly).

Every ref access is aligned to the ``(8, 128)`` int32 tiling, which is
what Mosaic can prove; a dynamic 1-D slice at an arbitrary offset is not.
Only the blocks a tile needs are in VMEM, so run length is bounded by HBM.

Keys are int64 split into (hi, lo) int32 planes (TPU int64 arithmetic is
emulated and slow; 2×int32 lexicographic compares are native).  Payload
seqnos ride along as a third int32 plane.  Stability: A wins ties, so
feeding runs oldest-first keeps duplicate keys seq-ascending.

Layout contract (enforced by ops.py): a run is packed as ``[3, G, 8,
128]`` (planes hi, lo, seq), padded with +inf sentinels to ``G - 1`` whole
blocks **plus one extra block of sentinels**, so every window is in bounds
and "run exhausted" needs no special casing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128                 # output tile: one lane row
SUB = 8                    # sublanes of an int32 VMEM tile
BLOCK = SUB * TILE         # keys per (8, 128) block of a packed run
PLANES = 3                 # hi, lo, seq
SENTINEL = jnp.iinfo(jnp.int32).max


def _lex_lt(a_hi, a_lo, b_hi, b_lo):
    """(a_hi, a_lo) < (b_hi, b_lo) lexicographic; lo planes are pre-biased
    (xor 0x80000000) so signed int32 compare == unsigned compare on raw."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def _lex_le(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _diagonals(a, b, n_tiles: int):
    """``a0[t]`` = number of A elements among the first ``t * TILE``
    merged outputs, for t in [0, n_tiles]: the largest a0 with
    A[a0-1] <= B[k0-a0], by a binary search run for every tile at once."""
    n_a = (a.shape[1] - 1) * BLOCK
    n_b = (b.shape[1] - 1) * BLOCK
    a_hi, a_lo = a[0].reshape(-1), a[1].reshape(-1)
    b_hi, b_lo = b[0].reshape(-1), b[1].reshape(-1)
    k0 = jnp.arange(n_tiles + 1, dtype=jnp.int32) * TILE
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


def _window_rows(blk0, blk1, start):
    """The two 128-lane rows holding elements [start, start + TILE) of a
    run, from the aligned blocks ``start // BLOCK`` and the one after:
    ``(rows, first_row)`` with ``rows[p] = (row0, row1)`` per plane."""
    first_row = start // TILE
    s = first_row % SUB
    sub = jax.lax.broadcasted_iota(jnp.int32, (2 * SUB, TILE), 0)
    pick0, pick1 = sub == s, sub == s + 1
    rows = []
    for p in range(PLANES):
        both = jnp.concatenate([blk0[p, 0], blk1[p, 0]], axis=0)
        rows.append(tuple(jnp.sum(jnp.where(pick, both, 0), axis=0,
                                  keepdims=True) for pick in (pick0, pick1)))
    return rows, first_row


def _as_column(row):
    """[1, TILE] lane row -> [TILE, TILE] with ``out[c, :] = row[c]``."""
    return jnp.broadcast_to(row, (TILE, TILE)).T


def _merge_kernel(diag_ref, a0_ref, a1_ref, b0_ref, b1_ref, out_ref):
    t = pl.program_id(0)
    k0 = t * TILE                      # global output rank of the tile start
    a_start, a_end = diag_ref[t], diag_ref[t + 1]
    b_start, b_end = k0 - a_start, k0 + TILE - a_end

    a_rows, a_row0 = _window_rows(a0_ref, a1_ref, a_start)
    b_rows, b_row0 = _window_rows(b0_ref, b1_ref, b_start)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)
    cand = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)

    def in_tile(idx, start, end):
        return (idx >= start) & (idx < end)

    # Candidates of this tile: A[a_start:a_end] and B[b_start:b_end], each
    # inside its two window rows; r indexes the row, idx the global index.
    a_idx = [(a_row0 + r) * TILE + lane for r in (0, 1)]
    b_idx = [(b_row0 + r) * TILE + lane for r in (0, 1)]
    a_cols = [(_as_column(a_rows[0][r]), _as_column(a_rows[1][r]),
               in_tile((a_row0 + r) * TILE + cand, a_start, a_end))
              for r in (0, 1)]
    b_cols = [(_as_column(b_rows[0][r]), _as_column(b_rows[1][r]),
               in_tile((b_row0 + r) * TILE + cand, b_start, b_end))
              for r in (0, 1)]

    def count(cols, hi, lo, before):
        n = jnp.zeros((1, TILE), jnp.int32)
        for c_hi, c_lo, c_ok in cols:
            hit = c_ok & before(c_hi, c_lo, hi, lo)
            n = n + jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)
        return n

    # (position within the output tile, valid, plane values) per cand row
    cands = []
    for r in (0, 1):
        pos = a_idx[r] - a_start + count(b_cols, a_rows[0][r], a_rows[1][r],
                                         _lex_lt)
        cands.append((pos, in_tile(a_idx[r], a_start, a_end),
                      [a_rows[p][r] for p in range(PLANES)]))
        pos = b_idx[r] - b_start + count(a_cols, b_rows[0][r], b_rows[1][r],
                                         _lex_le)
        cands.append((pos, in_tile(b_idx[r], b_start, b_end),
                      [b_rows[p][r] for p in range(PLANES)]))

    out_pos = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
    for p in range(PLANES):
        acc = jnp.zeros((TILE, 1), jnp.int32)
        for pos, ok, vals in cands:
            sel = ok & (pos == out_pos)          # [out position, candidate]
            acc = acc + jnp.sum(jnp.where(sel, vals[p], 0), axis=1,
                                keepdims=True)
        # the [TILE, 1] column back to a lane row
        out_ref[0, p:p + 1, :] = jnp.broadcast_to(acc, (TILE, TILE)).T[0:1, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_path_call(a, b, *, interpret: bool):
    """Stable merge of two packed runs (see the layout contract above).

    ``a``: ``[3, G_a, 8, 128]`` int32, ``b``: ``[3, G_b, 8, 128]``; the
    runs hold ``n_a = (G_a - 1) * BLOCK`` and ``n_b`` elements, sentinels
    included.  Returns the merged planes as ``[T, 3, 128]`` int32 output
    tiles, ``T = (n_a + n_b) / 128`` (real elements first, then
    sentinels).
    """
    assert a.shape[0] == PLANES and a.shape[2:] == (SUB, TILE)
    assert b.shape[0] == PLANES and b.shape[2:] == (SUB, TILE)
    g_a, g_b = a.shape[1], b.shape[1]
    n_tiles = (g_a + g_b - 2) * BLOCK // TILE
    diag = _diagonals(a, b, n_tiles)

    def a_block(off):
        return lambda t, d: (0, jnp.minimum(d[t] // BLOCK + off, g_a - 1),
                             0, 0)

    def b_block(off):
        return lambda t, d: (0, jnp.minimum((t * TILE - d[t]) // BLOCK + off,
                                            g_b - 1), 0, 0)

    blk = (PLANES, 1, SUB, TILE)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(blk, index) for index in
                  (a_block(0), a_block(1), b_block(0), b_block(1))],
        out_specs=pl.BlockSpec((1, PLANES, TILE), lambda t, d: (t, 0, 0)),
    )
    return pl.pallas_call(
        _merge_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, PLANES, TILE), jnp.int32),
        interpret=interpret,
    )(diag, a, a, b, b)
