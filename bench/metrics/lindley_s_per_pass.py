"""Host-clock seconds of the lindley phase, summed over the window's passes
and divided by their number; None where no pass has this phase."""


def read(r):
    spans = [p.phases["lindley"] for p in r.passes if "lindley" in p.phases]
    return sum(spans) / len(r.passes) if spans else None
