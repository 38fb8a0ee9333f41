"""Host wrapper for overlap_scan: plane splitting, bucket padding, numpy
entry."""

from __future__ import annotations

import numpy as np

from repro import obs

from ..merge_path.ops import split_planes
from ..platform import bucket, interpret_mode
from .kernel import BLOCK, TILE, fence_rank_call

_HI_SENT = np.int32(np.iinfo(np.int32).max)


def _pad_planes(vals: np.ndarray, n_pad: int) -> tuple[np.ndarray, ...]:
    hi, lo = split_planes(vals)
    H = np.full(n_pad, _HI_SENT, np.int32)
    L = np.full(n_pad, _HI_SENT, np.int32)
    H[:hi.shape[0]] = hi
    L[:lo.shape[0]] = lo
    return H, L


def fence_rank_np(fences: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """#fences <= key, per key (== np.searchsorted(fences, keys, 'right'))."""
    keys = np.asarray(keys, np.int64)
    if fences.shape[0] == 0:
        return np.zeros(keys.shape[0], np.int32)
    f_hi, f_lo = _pad_planes(fences, bucket(fences.shape[0], TILE))
    n_pad = bucket(keys.shape[0], BLOCK)
    k_hi, k_lo = (p.reshape(n_pad // TILE, TILE)
                  for p in _pad_planes(keys, n_pad))
    with obs.span("fence_rank.call"):
        out = np.asarray(fence_rank_call(f_hi, f_lo, k_hi, k_lo,
                                         interpret=interpret_mode()))
    if obs.enabled():
        obs.count("fence_rank.calls")
        obs.count("fence_rank.queries", keys.shape[0])
        obs.count("fence_rank.padded_queries", n_pad)
        obs.count("fence_rank.h2d_bytes", f_hi.nbytes + f_lo.nbytes
                  + k_hi.nbytes + k_lo.nbytes)
        obs.count("fence_rank.d2h_bytes", out.nbytes)
    return out.reshape(-1)[:keys.shape[0]]


def fence_rank_strict_np(fences: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """#fences < key, per key (== np.searchsorted(fences, keys, 'left')).

    Integer keys only: the strict rank is the inclusive rank of ``key - 1``.
    This is the second primitive ``repro.core.level_index`` needs for its
    ``pallas`` backend (start-of-overlap = strict rank of ``lo`` over the
    level's ``largest`` fences).
    """
    return fence_rank_np(fences, np.asarray(keys, np.int64) - 1)

