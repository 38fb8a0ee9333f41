"""LevelIndex: the vectorized fence/bloom manifest shared by every overlap
consumer in the store.

The paper's read-tail result hinges on how many SSTs a lookup probes per
level, and three different subsystems used to answer that question three
different ways: ``LSMTree.get`` walked per-level Python lists, compaction
picking re-scanned overlaps per candidate SST, and vSST planning ran fence
binary searches of its own.  This module centralizes the per-level fence
metadata once — flat numpy arrays (``smallest``, ``largest``, ``sizes``,
``uids``) mirroring each level's SST list, plus per-SST bloom seeds — and
serves every overlap/rank query from them, batched.

The arrays are maintained *incrementally* by the structural mutators
(flush appends to L0, ``_replace_in_level`` splices a contiguous span,
compaction removals delete by uid); queries never rebuild anything.

Rank queries are backend-switchable, mirroring ``repro.core.merge``:

* ``numpy``  — ``np.searchsorted``; the DES hot path.
* ``jnp``    — ``jnp.searchsorted`` under x64 (identical math on device).
* ``pallas`` — the ``repro.kernels.overlap_scan`` fence-rank TPU kernel
               (interpreted off the TPU); parity tests prove it drop-in.

Every query reduces to rank primitives over sorted int64 fences:
``rank_left(a, v) = #{a < v}`` and ``rank_right(a, v) = #{a <= v}``.  The SSTs
of a sorted disjoint level intersecting ``[lo, hi]`` are exactly positions
``[rank_left(largest, lo), rank_right(smallest, hi))``, and one inclusive rank
answers both ends.  The level's interleaved fence table ``F = [s0, l0, s1, l1,
...]`` is non-decreasing, since ``s_i <= l_i < s_{i+1}``, so with ``r =
rank_right(F, v)`` the level holds ``ceil(r / 2)`` smallest fences and
``floor(r / 2)`` largest fences at or below ``v``.  Keys are integers, so
``rank_left(largest, lo) = rank_right(F, lo - 1) // 2`` and
``rank_right(smallest, hi) = (rank_right(F, hi) + 1) // 2``: one rank of the
queries ``lo - 1`` and ``hi`` together, one device round trip on ``pallas``.
"""

from __future__ import annotations

import numpy as np

from .. import obs
from .sst import SST

_BACKEND = "numpy"


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("numpy", "jnp", "pallas")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


# Deterministic bloom-filter model: a (key, sst) pair pseudo-randomly false
# positives at the configured FPR.  The per-SST state is the mixed uid seed;
# identical to the scalar hash LSMTree._probe_sst historically used.
_KEY_MIX = np.uint64(0x9E3779B97F4A7C15)
_UID_MIX = np.uint64(0xBF58476D1CE4E5B9)
_MASK32 = np.uint64(0xFFFFFFFF)
_MAX32 = float(0xFFFFFFFF)


def bloom_seed_for_uid(uid) -> np.uint64:
    # wrap in Python ints: numpy warns on scalar uint64 overflow
    return np.uint64((int(uid) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF)


def bloom_false_positives(keys: np.ndarray, bloom_seed,
                          fpr: float) -> np.ndarray:
    """Boolean mask: which (key, sst) probes read a block despite a miss.

    ``bloom_seed`` is a scalar uint64 (one SST, many keys) or an array
    aligned with ``keys`` (one key per SST probe).
    """
    h = (np.asarray(keys).astype(np.uint64) * _KEY_MIX + bloom_seed) & _MASK32
    return (h.astype(np.float64) / _MAX32) < fpr


@obs.traced("manifest.rank")
def _rank(arr: np.ndarray, vals: np.ndarray, side: str,
          backend: str | None = None) -> np.ndarray:
    """Backend-routed searchsorted over a sorted int64 fence array.

    side='right' counts ``arr <= v``; side='left' counts ``arr < v``.
    ``backend`` overrides the module default (an index constructed with an
    explicit backend keeps it regardless of the global switch).
    """
    backend = backend or _BACKEND
    vals = np.asarray(vals, np.int64)
    if arr.shape[0] == 0:
        return np.zeros(vals.shape, np.int64)
    if backend == "numpy":
        return np.searchsorted(arr, vals, side=side).astype(np.int64)
    if backend == "jnp":
        import jax
        import jax.numpy as jnp
        with jax.enable_x64(True):
            out = jnp.searchsorted(jnp.asarray(arr, jnp.int64),
                                   jnp.asarray(vals, jnp.int64), side=side)
            return np.asarray(out, np.int64)
    from repro.kernels.overlap_scan.ops import (fence_rank_np,
                                                fence_rank_strict_np)
    rank = fence_rank_np if side == "right" else fence_rank_strict_np
    return rank(arr, vals.ravel()).astype(np.int64).reshape(vals.shape)


def _fields(ssts: list[SST]) -> tuple[np.ndarray, ...]:
    n = len(ssts)
    small = np.fromiter((s.smallest for s in ssts), np.int64, n)
    large = np.fromiter((s.largest for s in ssts), np.int64, n)
    sizes = np.fromiter((s.size for s in ssts), np.int64, n)
    uids = np.fromiter((s.uid for s in ssts), np.int64, n)
    return small, large, sizes, uids


class LevelIndex:
    """Flat fence/bloom arrays mirroring ``LSMTree.levels``.

    Position ``i`` in every array of ``level`` corresponds to
    ``levels[level][i]``; levels >= 1 are sorted by key and disjoint, L0 is
    FIFO (append order) and may overlap.
    """

    def __init__(self, n_levels: int, backend: str | None = None):
        assert backend in (None, "numpy", "jnp", "pallas")
        self.n_levels = n_levels
        self.backend = backend       # None -> follow the module switch
        z = lambda: np.empty(0, np.int64)  # noqa: E731
        self.smallest = [z() for _ in range(n_levels)]
        self.largest = [z() for _ in range(n_levels)]
        self.sizes = [z() for _ in range(n_levels)]
        self.uids = [z() for _ in range(n_levels)]
        self.bloom = [np.empty(0, np.uint64) for _ in range(n_levels)]
        self._csum: list[np.ndarray | None] = [None] * n_levels
        self._table: list[np.ndarray | None] = [None] * n_levels
        # Per-level mutation counter: bumps on every structural update so
        # derived caches (the tree's flat key/seq concatenation feeding
        # the vectorized GET path) can invalidate lazily.
        self.version = [0] * n_levels

    # ------------------------------------------------ incremental updates
    def _set(self, level: int, small, large, sizes, uids) -> None:
        self.smallest[level] = small
        self.largest[level] = large
        self.sizes[level] = sizes
        self.uids[level] = uids
        self.bloom[level] = (uids.astype(np.uint64) * _UID_MIX)
        self._csum[level] = None
        self._table[level] = None
        self.version[level] += 1

    def refresh(self, level: int, ssts: list[SST]) -> None:
        """Bulk rebuild of one level's arrays (init / recovery path)."""
        self._set(level, *_fields(ssts))

    def l0_append(self, sst: SST) -> None:
        self._set(0,
                  np.append(self.smallest[0], sst.smallest),
                  np.append(self.largest[0], sst.largest),
                  np.append(self.sizes[0], sst.size),
                  np.append(self.uids[0], sst.uid))

    def l0_popleft(self) -> None:
        self._set(0, self.smallest[0][1:], self.largest[0][1:],
                  self.sizes[0][1:], self.uids[0][1:])

    def l0_clear(self) -> None:
        z = np.empty(0, np.int64)
        self._set(0, z, z.copy(), z.copy(), z.copy())

    def splice(self, level: int, start: int, end: int,
               new_ssts: list[SST]) -> None:
        """Replace positions [start, end) with ``new_ssts`` (sorted)."""
        small, large, sizes, uids = _fields(new_ssts)
        self._set(level,
                  np.concatenate([self.smallest[level][:start], small,
                                  self.smallest[level][end:]]),
                  np.concatenate([self.largest[level][:start], large,
                                  self.largest[level][end:]]),
                  np.concatenate([self.sizes[level][:start], sizes,
                                  self.sizes[level][end:]]),
                  np.concatenate([self.uids[level][:start], uids,
                                  self.uids[level][end:]]))

    def remove_uids(self, level: int, uids: list[int]) -> None:
        keep = ~np.isin(self.uids[level], np.asarray(uids, np.int64))
        self._set(level, self.smallest[level][keep], self.largest[level][keep],
                  self.sizes[level][keep], self.uids[level][keep])

    # ------------------------------------------------------------ queries
    def n_ssts(self, level: int) -> int:
        return int(self.uids[level].shape[0])

    def fences(self, level: int) -> tuple[np.ndarray, np.ndarray]:
        """(smallest, largest) fence arrays of a sorted, disjoint level."""
        return self.smallest[level], self.largest[level]

    def fence_table(self, level: int) -> np.ndarray:
        """The interleaved fences ``[s0, l0, s1, l1, ...]`` of a sorted,
        disjoint level: non-decreasing, so one rank array (cached)."""
        if self._table[level] is None:
            table = np.empty(2 * self.n_ssts(level), np.int64)
            table[0::2] = self.smallest[level]
            table[1::2] = self.largest[level]
            self._table[level] = table
        return self._table[level]

    def overlap_ranges(self, level: int, lo: np.ndarray, hi: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Per-query position slices [start, end) of the level's SSTs
        intersecting [lo_i, hi_i] (requires lo <= hi elementwise): one
        inclusive rank of ``lo - 1`` and ``hi`` over the fence table."""
        lo = np.asarray(lo, np.int64)
        r = _rank(self.fence_table(level),
                  np.concatenate([lo - 1, np.asarray(hi, np.int64)]),
                  "right", self.backend)
        n = lo.shape[0]
        return r[:n] // 2, (r[n:] + 1) // 2

    def overlap_slice(self, level: int, lo: int, hi: int) -> tuple[int, int]:
        s, e = self.overlap_ranges(level, np.asarray([lo], np.int64),
                                   np.asarray([hi], np.int64))
        return int(s[0]), int(e[0])

    def overlap_counts(self, level: int, lo: np.ndarray, hi: np.ndarray
                       ) -> np.ndarray:
        """#SSTs of ``level`` intersecting each [lo_i, hi_i] (the §4.2
        overlap quantity, vs this level's fences)."""
        starts, ends = self.overlap_ranges(level, lo, hi)
        return np.maximum(0, ends - starts)

    def scan_spans(self, level: int, start_keys: np.ndarray,
                   nbytes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Per-scan SST position spans [start_i, end_i) of a sorted level
        covering a forward range scan: from the first SST whose range can
        contain ``start_keys[i]`` (the same backend-routed fence rank that
        answers point overlaps) until the span holds >= ``nbytes[i]`` of
        data or the level ends."""
        starts = _rank(self.largest[level], start_keys, "left", self.backend)
        n = self.n_ssts(level)
        if n == 0:
            return starts, starts
        csum = self.size_prefix(level)
        need = csum[np.minimum(starts, n)] + np.asarray(nbytes, np.int64)
        ends = np.searchsorted(csum, need, side="left").astype(np.int64)
        return starts, np.clip(ends, starts, n)

    def size_prefix(self, level: int) -> np.ndarray:
        """csum[i] = total bytes of the level's first i SSTs (cached)."""
        if self._csum[level] is None:
            self._csum[level] = np.concatenate(
                [np.zeros(1, np.int64), np.cumsum(self.sizes[level])])
        return self._csum[level]

    def overlap_bytes(self, src_level: int, dst_level: int) -> np.ndarray:
        """Per src-SST: bytes of dst_level SSTs its key range intersects —
        the compaction-picking score numerator, one batched query."""
        starts, ends = self.overlap_ranges(dst_level, self.smallest[src_level],
                                           self.largest[src_level])
        csum = self.size_prefix(dst_level)
        return csum[ends] - csum[starts]

    # -------------------------------------------------------- validation
    def check_against(self, levels: list[list[SST]]) -> None:
        """Invariant: the mirror is in lock-step with the SST lists."""
        for level, ssts in enumerate(levels):
            small, large, sizes, uids = _fields(ssts)
            assert np.array_equal(self.smallest[level], small), \
                f"LevelIndex.smallest out of sync at L{level}"
            assert np.array_equal(self.largest[level], large), \
                f"LevelIndex.largest out of sync at L{level}"
            assert np.array_equal(self.sizes[level], sizes), \
                f"LevelIndex.sizes out of sync at L{level}"
            assert np.array_equal(self.uids[level], uids), \
                f"LevelIndex.uids out of sync at L{level}"
