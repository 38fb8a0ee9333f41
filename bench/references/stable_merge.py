"""Reference of a compaction merge of two sorted runs: a stable merge
(ties: the first, older run first).  ``differs(args, out)`` is the check
of one call of a kernel whose file names ``"reference": "stable_merge"``;
``args`` are the call's ``(a_keys, a_seqs, b_keys, b_seqs)``."""

import numpy as np


def stable_merge(a_keys, a_seqs, b_keys, b_seqs):
    keys = np.concatenate([np.asarray(a_keys, np.int64),
                           np.asarray(b_keys, np.int64)])
    seqs = np.concatenate([np.asarray(a_seqs, np.int64),
                           np.asarray(b_seqs, np.int64)])
    order = np.argsort(keys, kind="stable")
    return keys[order], seqs[order]


def differs(args, out) -> bool:
    k, s = stable_merge(*args)
    got_k, got_s = (np.asarray(x, np.int64) for x in out)
    return not (np.array_equal(k, got_k) and np.array_equal(s, got_s))
