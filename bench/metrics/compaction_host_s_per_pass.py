"""Self time of the program's ``lsm.flush``, ``lsm.background`` and
``lsm.chain`` spans (flushes, compaction picking, SST splits and level
splices, less the merges and fence ranks inside), host clock, per pass."""

from lsmbench.recorder import per_pass, self_s


def read(r):
    return per_pass(r, self_s("lsm.flush", "lsm.background", "lsm.chain"))
