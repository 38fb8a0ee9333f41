"""Host-clock time of the program's ``sim.setup`` spans: the run's
prologue (routing ops to shards, the memtable fill schedule), per pass."""

from lsmbench.recorder import per_pass, total_s


def read(r):
    return per_pass(r, total_s("sim.setup"))
