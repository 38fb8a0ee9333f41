"""Host wrapper around the merge-path kernel: int64 <-> (hi, lo) planes,
sentinel padding to power-of-two buckets, and the numpy entry used by the
LSM core's ``pallas`` merge backend."""

from __future__ import annotations

import numpy as np

from repro import obs

from ..platform import bucket, interpret_mode
from .kernel import BLOCK, LANES, PLANES, SENTINEL, SUB, merge_path_call


def split_planes(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 key -> (hi, lo) int32 planes with order-preserving lo bias.

    hi = key >> 32 (arithmetic); lo = bit-reinterpret((key & 0xffffffff)
    ^ 0x80000000) so a *signed* int32 compare on lo matches the unsigned
    compare on the raw low word; (hi, lo) lexicographic == int64 order.
    """
    keys = np.asarray(keys, np.int64)
    hi = (keys >> 32).astype(np.int32)
    raw = (keys & 0xFFFF_FFFF).astype(np.uint32)
    lo = (raw ^ np.uint32(0x8000_0000)).view(np.int32)
    return hi, np.ascontiguousarray(lo)


def join_planes(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    hi = np.asarray(hi, np.int64)
    raw = (np.ascontiguousarray(np.asarray(lo, np.int32)).view(np.uint32)
           ^ np.uint32(0x8000_0000)).astype(np.int64)
    return (hi << 32) | raw


def _pack_run(keys: np.ndarray, seqs: np.ndarray) -> np.ndarray:
    """A run as the kernel's ``[3, G, 8, 128]`` planes: bucket-padded with
    sentinels, plus one whole sentinel block for the window loads."""
    n = keys.shape[0]
    total = bucket(n, BLOCK) + BLOCK
    buf = np.empty((PLANES, total), np.int32)
    buf[:2, n:] = SENTINEL
    buf[2, n:] = 0
    buf[0, :n], buf[1, :n] = split_planes(keys)
    buf[2, :n] = seqs
    return buf.reshape(PLANES, total // BLOCK, SUB, LANES)


def merge_two_runs_np(a_keys: np.ndarray, a_seqs: np.ndarray,
                      b_keys: np.ndarray, b_seqs: np.ndarray
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Stable merge of two sorted int64 runs via the TPU kernel (ties: A
    first; interpreted off the TPU).  Seqnos must fit int32."""
    n, m = int(a_keys.shape[0]), int(b_keys.shape[0])
    if n == 0:
        return np.asarray(b_keys, np.int64), np.asarray(b_seqs, np.int64)
    if m == 0:
        return np.asarray(a_keys, np.int64), np.asarray(a_seqs, np.int64)
    if not (np.all(np.abs(a_seqs) < 2**31) and np.all(np.abs(b_seqs) < 2**31)):
        raise ValueError("merge_path carries seqnos as int32")
    with obs.span("merge_path.pack"):
        a, b = _pack_run(a_keys, a_seqs), _pack_run(b_keys, b_seqs)
    # the kernel merges both buckets; the extra sentinel block of each
    # packed run is shipped but not merged
    padded, h2d = (a.shape[1] + b.shape[1] - 2) * BLOCK, a.nbytes + b.nbytes
    with obs.span("merge_path.call"):
        out = merge_path_call(a, b, interpret=interpret_mode())
        del a, b                # free the packed runs before the copy back
        out = np.asarray(out)
    if obs.enabled():
        obs.count("merge_path.calls")
        obs.count("merge_path.keys", n + m)
        obs.count("merge_path.padded_keys", padded)
        obs.count("merge_path.h2d_bytes", h2d)
        obs.count("merge_path.d2h_bytes", out.nbytes)
    with obs.span("merge_path.unpack"):
        planes = out.reshape(PLANES, -1)[:, :n + m]
        return join_planes(planes[0], planes[1]), planes[2].astype(np.int64)
