"""Bytes the program ships to the device per pass: the ``h2d_bytes``
counters of the merge, fence-rank and Lindley kernels, summed."""

from lsmbench.recorder import kernel_bytes, per_pass


def read(r):
    return per_pass(r, kernel_bytes("h2d_bytes"))
