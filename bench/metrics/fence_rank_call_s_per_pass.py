"""Host-clock time of the program's ``fence_rank.call`` spans: each
fence-rank kernel's launch, transfers in, device time and copy back,
without the packing of fences and keys around it, per pass."""

from lsmbench.recorder import per_pass, total_s


def read(r):
    return per_pass(r, total_s("fence_rank.call"))
