"""Host-clock seconds of the temporal phase, summed over the window's passes
and divided by their number; None where no pass has this phase."""


def read(r):
    spans = [p.phases["temporal"] for p in r.passes if "temporal" in p.phases]
    return sum(spans) / len(r.passes) if spans else None
