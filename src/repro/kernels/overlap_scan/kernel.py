"""Batched fence-pointer rank counts as a Pallas TPU kernel.

The paper's §4.2 look-ahead policy probes, for *every appended key*, the
overlap of the in-flight vSST with the L2 fence table — the per-key CPU
hot-spot the authors call out in §6.3.  A GPU port would binary-search per
thread; the TPU-native shape is **brute-force block counting**: a [128
fences × 128 keys] comparison tile is a handful of VPU ops, so counting
``#fences <= key`` over fence tiles beats a gather-heavy binary search
for the fence tables an LSM manifest holds (one fence per SST).

Keys/fences are int64 split into (hi, lo) int32 planes (same convention as
``merge_path``).  Keys are lane rows of a ``[n_keys / 128, 128]`` array;
fences come as columns — row ``j`` of the ``[n_fences, 128]`` fence planes
is fence ``j`` repeated across the lanes — so a fence tile compares with a
key row without any relayout.  Grid: (key blocks of ``8 × 128``, fence
tiles of 128); the count block stays resident across the fence axis and
accumulates.  Every access is aligned to the int32 ``(8, 128)`` tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 128                 # keys per lane row, fences per fence tile
SUB = 8                    # key rows per grid step
BLOCK = SUB * TILE         # keys per grid step


def _lex_le(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _rank_kernel(f_hi_ref, f_lo_ref, k_hi_ref, k_lo_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    f_hi = f_hi_ref[...]                       # [fences, lanes]
    f_lo = f_lo_ref[...]
    for r in range(SUB):
        le = _lex_le(f_hi, f_lo, k_hi_ref[r:r + 1, :], k_lo_ref[r:r + 1, :])
        out_ref[r:r + 1, :] += jnp.sum(le.astype(jnp.int32), axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fence_rank_call(f_hi, f_lo, k_hi, k_lo, *, interpret: bool):
    """counts[i] = #{j : fence_j <= key_i}.

    ``f_hi``/``f_lo``: ``[n_fences]`` int32 planes, ``n_fences`` a TILE
    multiple padded with +inf sentinels; ``k_hi``/``k_lo``: ``[n_keys /
    128, 128]``, ``n_keys`` a BLOCK multiple.  Sentinel fences count only
    for sentinel keys, which the ops layer slices away.
    """
    n_fences = f_hi.shape[0]
    rows = k_hi.shape[0]
    assert n_fences % TILE == 0 and rows % SUB == 0
    f_cols = [jnp.broadcast_to(f[:, None], (n_fences, TILE))
              for f in (f_hi, f_lo)]
    fence_spec = pl.BlockSpec((TILE, TILE), lambda i, j: (j, 0))
    key_spec = pl.BlockSpec((SUB, TILE), lambda i, j: (i, 0))
    return pl.pallas_call(
        _rank_kernel,
        grid=(rows // SUB, n_fences // TILE),
        in_specs=[fence_spec, fence_spec, key_spec, key_spec],
        out_specs=key_spec,
        out_shape=jax.ShapeDtypeStruct((rows, TILE), jnp.int32),
        interpret=interpret,
    )(*f_cols, k_hi, k_lo)
