"""Reference of a manifest fence rank: the count of fences at or below
each key.  ``differs(args, out)`` is the check of one call of a kernel
whose file names ``"reference": "rank_at_or_below"``; ``args`` are the
call's ``(fences, keys)``."""

import numpy as np


def rank_at_or_below(fences, keys):
    return np.searchsorted(np.asarray(fences, np.int64),
                           np.asarray(keys, np.int64), side="right")


def differs(args, out) -> bool:
    want = rank_at_or_below(*args)
    got = np.asarray(out, np.int64).reshape(-1)
    return not np.array_equal(want, got)
