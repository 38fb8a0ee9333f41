"""Share of padding in the ops the departure scan runs over, in percent:
the program's ``lindley.padded_ops`` (each queue's power-of-two bucket)
less ``lindley.ops``, over the former."""

from lsmbench.recorder import share


def read(r):
    return share("lindley.ops", "lindley.padded_ops")
