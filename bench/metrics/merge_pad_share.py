"""Share of padding in the keys the merge kernel merges, in percent: the
program's ``merge_path.padded_keys`` (both runs' power-of-two buckets)
less ``merge_path.keys`` (their real keys), over the former."""

from lsmbench.recorder import share


def read(r):
    return share("merge_path.keys", "merge_path.padded_keys")
