"""Device seconds of the manifest's fence-rank programs in the trace,
divided by the window's passes."""


def read(r):
    if r.reduced is None or not r.passes:
        return None
    device_s = r.reduced.program_s.get("fence_rank", 0.0)
    return device_s / len(r.passes) if device_s > 0 else None
