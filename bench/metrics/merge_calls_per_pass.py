"""Merge kernel calls per pass (the program's ``merge_path.calls``
counter)."""

from lsmbench.recorder import counter, per_pass


def read(r):
    return per_pass(r, counter("merge_path.calls"))
