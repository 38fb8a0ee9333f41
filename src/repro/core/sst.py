"""Sorted String Tables backed by numpy arrays.

An SST is an immutable sorted run of (key, seq) pairs.  Values are implicit:
the KV store's correctness contract is "a GET returns the payload written by
the highest-seqno PUT", so carrying the seqno is sufficient to verify
latest-wins semantics end-to-end (tests derive the payload as hash(key, seq)).
Physical size is ``n_keys * kv_size`` bytes, matching the paper's fixed-size
KV pairs (200 B in §5).
"""

from __future__ import annotations

import itertools
from contextlib import contextmanager

import numpy as np

_ids = itertools.count()
# uid-allocator override stack: when a tree routes SST identity through its
# own counter (trees beyond fleet slot 0 — see LSMTree), the top of this
# stack replaces the module counter for SSTs created inside the scope.
# Keeping slot 0 on the module counter preserves every single-tree uid
# stream byte-for-byte (the bloom-FP hash mixes sst.uid, and the
# read-parity capture pins those streams).
_alloc_stack: list = []


@contextmanager
def uid_allocator(src):
    """Scope SST uid assignment to ``src`` (an iterator; None keeps the
    process-global counter).  Trees wrap their structural entry points in
    this so a fleet's SST identities do not depend on the engine's
    event-interleaving order across trees."""
    if src is None:
        yield
        return
    _alloc_stack.append(src)
    try:
        yield
    finally:
        _alloc_stack.pop()


class SST:
    __slots__ = ("keys", "seqs", "kv_size", "uid", "n", "size", "smallest",
                 "largest")

    def __init__(self, keys: np.ndarray, seqs: np.ndarray, kv_size: int):
        assert keys.ndim == 1 and keys.shape == seqs.shape
        self.keys = keys
        self.seqs = seqs
        self.kv_size = kv_size
        self.uid = next(_alloc_stack[-1]) if _alloc_stack else next(_ids)
        # SSTs are immutable: metadata is materialized once (these fields
        # are on the structural hot path — total_size / fence rebuilds).
        n = int(keys.shape[0])
        self.n = n
        self.size = n * kv_size
        if n:
            self.smallest = int(keys[0])
            self.largest = int(keys[-1])
        else:
            self.smallest, self.largest = 0, -1   # empty range

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"SST#{self.uid}[{self.smallest}..{self.largest}] n={self.n}"

    # ----------------------------------------------------------------- query
    def get(self, key: int) -> int | None:
        """Return seqno for key or None."""
        i = int(np.searchsorted(self.keys, key))
        if i < self.n and int(self.keys[i]) == key:
            return int(self.seqs[i])
        return None

    def may_contain(self, key: int) -> bool:
        return self.smallest <= key <= self.largest

    def scan_from(self, key: int, m: int) -> tuple[np.ndarray, np.ndarray]:
        """Up to ``m`` (keys, seqs) entries with key >= ``key``."""
        i = int(np.searchsorted(self.keys, key))
        return self.keys[i:i + m], self.seqs[i:i + m]

    def check_invariants(self) -> None:
        assert self.n > 0, "empty SST"
        d = np.diff(self.keys)
        assert np.all(d > 0), "SST keys must be strictly increasing"


def sst_from_sorted(keys: np.ndarray, seqs: np.ndarray, kv_size: int) -> SST:
    return SST(np.ascontiguousarray(keys), np.ascontiguousarray(seqs), kv_size)


def split_fixed(keys: np.ndarray, seqs: np.ndarray, kv_size: int,
                sst_size: int) -> list[SST]:
    """Split a sorted run into fixed-size SSTs of at most ``sst_size`` bytes."""
    per = max(1, sst_size // kv_size)
    out = []
    for i in range(0, keys.shape[0], per):
        out.append(SST(keys[i:i + per], seqs[i:i + per], kv_size))
    return out


def total_size(ssts: list[SST]) -> int:
    return sum(s.size for s in ssts)


def overlapping(ssts: list[SST], lo: int, hi: int) -> list[SST]:
    """SSTs from a *sorted, disjoint* level whose range intersects [lo, hi].

    The list-level oracle for the store's manifest queries: the LSM core
    itself routes through ``repro.core.level_index.LevelIndex``, which
    answers the same span with one rank over its interleaved fence table —
    the span is [first SST with largest >= lo, first SST with smallest > hi).
    """
    if not ssts:
        return []
    smallest = np.fromiter((s.smallest for s in ssts), np.int64, len(ssts))
    largest = np.fromiter((s.largest for s in ssts), np.int64, len(ssts))
    start = int(np.searchsorted(largest, lo, side="left"))
    end = int(np.searchsorted(smallest, hi, side="right"))
    return ssts[start:end]


def level_check_disjoint(ssts: list[SST]) -> None:
    """Invariant: leveled runs are sorted by key and pairwise disjoint."""
    for a, b in zip(ssts, ssts[1:]):
        assert a.largest < b.smallest, (
            f"overlapping leveled SSTs: {a} vs {b}")
