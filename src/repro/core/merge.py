"""Sorted-run merge backends.

Compaction is the paper's compute hot-spot; the core calls through this
module so the backend can be swapped:

* ``numpy``  — fast CPU path used by the discrete-event simulation.
* ``jnp``    — pure-jnp formulation (identical math to the Pallas oracle).
* ``pallas`` — the TPU merge-path kernel (``repro.kernels.merge_path``):
               compiled on a TPU, interpreted elsewhere (where tests prove
               it a drop-in for the store's merge).

All backends implement *latest-wins k-run merge*: runs are given newest
first; on duplicate keys the entry from the newest run (or the highest seq)
survives.  Within a single run keys are unique by construction.
"""

from __future__ import annotations

import numpy as np

_BACKEND = "numpy"


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("numpy", "jnp", "pallas")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def merge_runs(runs: list[tuple[np.ndarray, np.ndarray]]
               ) -> tuple[np.ndarray, np.ndarray]:
    """Merge k sorted (keys, seqs) runs, dedup latest-wins by max seq.

    Seqs are globally unique and increase over time, so "latest wins" is
    exactly "max seq wins" — independent of run order.
    """
    runs = [r for r in runs if r[0].size]
    if not runs:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    if len(runs) == 1:
        return runs[0]
    if _BACKEND == "numpy":
        return _merge_numpy(runs)
    if _BACKEND == "jnp":
        return _merge_jnp(runs)
    return _merge_pallas(runs)


def _dedup_latest(keys: np.ndarray, seqs: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Given key-sorted, seq-ascending-within-key arrays, keep max-seq entry."""
    last = np.ones(keys.shape[0], dtype=bool)
    last[:-1] = keys[1:] != keys[:-1]
    return keys[last], seqs[last]


def _merge_numpy(runs) -> tuple[np.ndarray, np.ndarray]:
    keys = np.concatenate([r[0] for r in runs])
    seqs = np.concatenate([r[1] for r in runs])
    # Sort by (key, seq) so the last duplicate has the highest seq.
    order = np.lexsort((seqs, keys))
    return _dedup_latest(keys[order], seqs[order])


def _merge_jnp(runs) -> tuple[np.ndarray, np.ndarray]:
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):             # keys are true int64
        keys = jnp.concatenate([jnp.asarray(r[0], jnp.int64) for r in runs])
        seqs = jnp.concatenate([jnp.asarray(r[1], jnp.int64) for r in runs])
        order = jnp.lexsort((seqs, keys))
        k, s = np.asarray(keys[order]), np.asarray(seqs[order])
    return _dedup_latest(k, s)


def _disjoint_groups(runs) -> list[tuple[np.ndarray, np.ndarray]]:
    """Fold consecutive runs whose key ranges are pairwise disjoint (the
    SSTs of one sorted level) into one run each, concatenated in key
    order.  A key occurs at most once per group and groups keep the
    runs' order, so merging groups in order sees duplicates in the same
    age order as merging the runs one by one."""
    groups: list[list[tuple[np.ndarray, np.ndarray]]] = []
    for run in runs:
        lo, hi = run[0][0], run[0][-1]
        if groups and all(hi < k[0] or lo > k[-1] for k, _ in groups[-1]):
            groups[-1].append(run)
        else:
            groups.append([run])
    out = []
    for g in groups:
        g.sort(key=lambda r: r[0][0])
        out.append((np.concatenate([r[0] for r in g]),
                    np.concatenate([r[1] for r in g])))
    return out


def _merge_pallas(runs) -> tuple[np.ndarray, np.ndarray]:
    """Reduce pairwise with the TPU merge-path kernel, one launch per
    group of disjoint runs.

    The kernel performs a *stable* merge (ties: left run first), so feeding
    runs oldest-first keeps duplicate keys seq-ascending, which is what
    ``_dedup_latest`` needs.  (For a given key, a newer run's entry always
    carries a higher seqno.)
    """
    from repro.kernels.merge_path import ops as mp_ops

    ordered = _disjoint_groups(runs[::-1])  # oldest first
    acc_k, acc_s = ordered[0]
    for k, s in ordered[1:]:
        acc_k, acc_s = mp_ops.merge_two_runs_np(acc_k, acc_s, k, s)
    return _dedup_latest(np.asarray(acc_k), np.asarray(acc_s))
