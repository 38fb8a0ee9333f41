"""Self time of the program's ``sim.apply_window`` spans (memtable writes
and each window's GET batch, less the fence ranks inside), host clock,
summed over the window and divided by its passes."""

from lsmbench.recorder import per_pass, self_s


def read(r):
    return per_pass(r, self_s("sim.apply_window"))
