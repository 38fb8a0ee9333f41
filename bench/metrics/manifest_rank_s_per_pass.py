"""Host-clock time of the program's ``manifest.rank`` spans: every fence
rank query of the manifest, kernel calls included, per pass."""

from lsmbench.recorder import per_pass, total_s


def read(r):
    return per_pass(r, total_s("manifest.rank"))
