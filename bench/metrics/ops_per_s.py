"""Ops of every pass completed in the window, over the window's wall
time (host clock).  Every op of a pass counts: each pass produces the
simulated latency of every op of its stream."""


def read(r):
    return sum(p.ops for p in r.passes) / r.window_s if r.passes else None
