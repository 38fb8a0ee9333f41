"""Host-clock seconds of the structural phase, summed over the window's passes
and divided by their number; None where no pass has this phase."""


def read(r):
    spans = [p.phases["structural"] for p in r.passes if "structural" in p.phases]
    return sum(spans) / len(r.passes) if spans else None
