"""What the program's own recorder (``repro.obs``) holds after a traced
window, for the metric readers that read its spans and counters.

The recorder is on only while JAX's profiler runs, so with ``--trace 1``
it holds the window's passes and nothing else.  Where the program has no
recorder, or it recorded nothing, every function here gives None.
"""

from __future__ import annotations

#: the kernels whose host entries count calls, sizes and bytes
KERNELS = ("merge_path", "fence_rank", "lindley")


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def self_s(*names: str) -> float | None:
    """The summed self time of the named spans; None where none ran."""
    obs = _obs()
    got = obs.self_seconds() if obs else {}
    return sum(got[n] for n in names if n in got) \
        if any(n in got for n in names) else None


def total_s(name: str) -> float | None:
    obs = _obs()
    return (obs.total_seconds() if obs else {}).get(name)


def counter(name: str) -> int | None:
    obs = _obs()
    return (obs.counters() if obs else {}).get(name)


def per_pass(r, value):
    """``value`` over the window's passes; None where there is none."""
    return value / len(r.passes) if value is not None and r.passes else None


def share(real: str, padded: str) -> float | None:
    """Padding's share of counter ``padded``, in percent, where counter
    ``real`` holds the real part; None where nothing was padded."""
    p, n = counter(padded), counter(real)
    return 100.0 * (p - n) / p if p and n is not None else None


def kernel_bytes(what: str) -> int | None:
    """Counter ``<kernel>.<what>`` summed over :data:`KERNELS`; None where
    no kernel counted it."""
    got = [v for k in KERNELS if (v := counter(f"{k}.{what}")) is not None]
    return sum(got) if got else None
