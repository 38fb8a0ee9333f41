"""Share of padding in the keys the fence-rank kernel ranks, in percent:
the program's ``fence_rank.padded_queries`` (each batch's power-of-two
bucket) less ``fence_rank.queries``, over the former."""

from lsmbench.recorder import share


def read(r):
    return share("fence_rank.queries", "fence_rank.padded_queries")
