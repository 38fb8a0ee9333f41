"""Host-clock time of the program's ``merge_path.call`` spans: each merge
kernel's launch, transfers in, device time and copy back, per pass."""

from lsmbench.recorder import per_pass, total_s


def read(r):
    return per_pass(r, total_s("merge_path.call"))
