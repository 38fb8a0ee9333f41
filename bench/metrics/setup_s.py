"""Process start to the first timed pass: imports, the stream, and the
warm-up passes that compile or load every program the window uses (host
clock)."""


def read(r):
    return r.setup_s
