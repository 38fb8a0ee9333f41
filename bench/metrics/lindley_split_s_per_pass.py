"""Host-clock time of the program's ``lindley.split`` spans: splitting the
padded float64 services and arrivals into float32 pairs for the kernel,
per pass."""

from lsmbench.recorder import per_pass, total_s


def read(r):
    return per_pass(r, total_s("lindley.split"))
