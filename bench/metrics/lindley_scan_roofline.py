"""Share of the Lindley scan's least time in its device time, in percent.

Least time: 24 B per op (its arrival and service read, its departure
written, each a float64) at the chip's HBM bandwidth, over every op of
the window's passes.  Device time: the summed duration of the Lindley
programs' events in the trace."""

BYTES_PER_OP = 24


def read(r):
    if r.reduced is None:
        return None
    device_s = r.reduced.program_s.get("lindley_scan", 0.0)
    ops = sum(p.ops for p in r.passes)
    if device_s <= 0 or ops <= 0:
        return None
    return 100.0 * ops * BYTES_PER_OP / r.peaks["hbm_bytes_per_s"] / device_s
