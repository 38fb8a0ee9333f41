"""Share of the merge layer's least time in its device time, in percent.

Least time: the bytes a compaction merge must move at the chip's HBM
bandwidth.  Each compacted key is an int64 key and an int64 sequence
number, read once and written once: 16 B for every key the window's
compactions read and every key they wrote (their job logs'
``bytes_read`` and ``bytes_written`` over the record size).  Device time:
the summed duration of the merge programs' events in the trace, the
diagonal search and the kernel both."""

BYTES_PER_KEY = 16


def read(r):
    if r.reduced is None:
        return None
    device_s = r.reduced.program_s.get("merge_path", 0.0)
    keys = sum(p.compaction_keys for p in r.passes)
    if device_s <= 0 or keys <= 0:
        return None
    least_s = keys * BYTES_PER_KEY / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
