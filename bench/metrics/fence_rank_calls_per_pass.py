"""Fence-rank kernel calls per pass (the program's ``fence_rank.calls``
counter)."""

from lsmbench.recorder import counter, per_pass


def read(r):
    return per_pass(r, counter("fence_rank.calls"))
