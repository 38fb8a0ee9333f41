"""Host time of the departure scan around its kernel: the program's
``lindley.batch`` spans less their ``lindley.call`` spans (row fill,
double-f32 split, the sum of the halves), per pass."""

from lsmbench.recorder import per_pass, total_s


def read(r):
    batch, call = total_s("lindley.batch"), total_s("lindley.call")
    return per_pass(r, batch - (call or 0.0) if batch is not None else None)
