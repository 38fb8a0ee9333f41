"""Host time of the compaction merges around the kernel: self time of the
program's ``lsm.merge`` spans (run grouping, dedup) plus its
``merge_path.pack`` and ``merge_path.unpack`` spans, per pass."""

from lsmbench.recorder import per_pass, self_s


def read(r):
    return per_pass(r, self_s("lsm.merge", "merge_path.pack",
                              "merge_path.unpack"))
