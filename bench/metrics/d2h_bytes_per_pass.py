"""Bytes the program copies back from the device per pass: the
``d2h_bytes`` counters of the merge, fence-rank and Lindley kernels,
summed."""

from lsmbench.recorder import kernel_bytes, per_pass


def read(r):
    return per_pass(r, kernel_bytes("d2h_bytes"))
