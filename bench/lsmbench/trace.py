"""The profiler trace of a window, and its reduction to numbers.

:class:`Profile` starts JAX's profiler around the window and reads the
``.xplane.pb`` it writes into a :class:`Trace`: the device's events (one
line of whole programs, one of the operations inside them) and the host
spans the harness wrote with ``TraceAnnotation`` (the window and each
phase of each pass), all on the profiler's one clock.

The reduction is plain arithmetic on a :class:`Trace`, so it can be
checked on a small recorded trace (``bench/tests/data/trace_small.json``):

* busy time: the union of the device's operation intervals inside the
  window; idle share is one minus busy over the window;
* per-program device time: the summed durations of the program events
  whose name starts with one of a kernel's program names;
* idle time by host phase: for each phase span of each pass, its length
  less the busy time inside it, summed by phase name; what no phase
  covers is "between phases".
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from pathlib import Path

#: host span names the harness writes; "window" brackets the measured window
PHASES = ("structural", "temporal", "lindley", "finalize")
WINDOW = "window"
#: device lines: whole programs, and the operations inside them
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclass
class Trace:
    """Events in nanoseconds on the profiler's clock."""

    programs: list[tuple[str, float, float]] = field(default_factory=list)
    ops: list[tuple[str, float, float]] = field(default_factory=list)
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    @staticmethod
    def from_json(d: dict) -> "Trace":
        return Trace(*([tuple(e) for e in d[k]]
                       for k in ("programs", "ops", "spans")))


def read_xplane(path: str, device_prefix: str = "/device:TPU:0") -> Trace:
    """The first chip's program and op lines, and the harness's spans."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    names = set(PHASES) | {WINDOW}
    for plane in data.planes:
        if plane.name == device_prefix:
            for line in plane.lines:
                if line.name == PROGRAM_LINE:
                    tr.programs.extend((e.name, float(e.start_ns),
                                        float(e.duration_ns))
                                       for e in line.events)
                elif line.name == OP_LINE:
                    # an op's event name is its whole HLO line; keep the
                    # instruction's name ("%merge_path_call.1")
                    tr.ops.extend((e.name.split(" = ")[0],
                                   float(e.start_ns), float(e.duration_ns))
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((e.name, float(e.start_ns),
                                 float(e.duration_ns))
                                for e in line.events if e.name in names)
    return tr


class Profile:
    """JAX's profiler over one window, host Python tracing off."""

    def __init__(self, log_dir: Path):
        self.log_dir = log_dir

    def __enter__(self) -> "Profile":
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.profiler.stop_trace()

    def read(self) -> Trace:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.log_dir}")
        return read_xplane(found[-1])


# ------------------------------------------------------------- reduction
def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Reduced:
    """What the metric readers take from one trace, in seconds."""

    window_s: float
    busy_s: float
    program_s: dict[str, float]
    top_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_bounds(tr: Trace) -> tuple[float, float]:
    spans = [(s, s + d) for n, s, d in tr.spans if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(spans)}")
    return spans[0]


def program_seconds(tr: Trace, prefixes: tuple[str, ...], lo: float,
                    hi: float) -> float:
    """Summed duration of the program events inside ``[lo, hi]`` whose
    name starts with one of ``prefixes``."""
    return sum(d for n, s, d in tr.programs
               if s >= lo and s + d <= hi and n.startswith(prefixes)) * 1e-9


class Busy:
    """The union of busy intervals, with the busy time up to any instant."""

    def __init__(self, intervals: list[tuple[float, float]]):
        self.iv = union(intervals)
        self.starts = [a for a, _b in self.iv]
        self.before = [0.0]
        for a, b in self.iv:
            self.before.append(self.before[-1] + b - a)

    @property
    def total(self) -> float:
        return self.before[-1]

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        a, b = self.iv[i - 1]
        return self.before[i - 1] + min(t, b) - a

    def within(self, lo: float, hi: float) -> float:
        return self.upto(hi) - self.upto(lo)


def reduce(tr: Trace, kernels: dict[str, tuple[str, ...]],
           top: int = 10) -> Reduced:
    """Busy and idle time of the window, device seconds per kernel, the
    operations that took most time, and the idle time by what the host
    was doing: each phase span's length less the busy time inside it."""
    lo, hi = window_bounds(tr)
    busy = Busy(clip([(s, s + d) for _n, s, d in tr.ops], lo, hi))
    per_op: dict[str, float] = {}
    for n, s, d in tr.ops:
        if s >= lo and s + d <= hi:
            per_op[n] = per_op.get(n, 0.0) + d * 1e-9
    idle: dict[str, float] = {}
    for n, a, b in ((n, s, s + d) for n, s, d in tr.spans if n != WINDOW):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            idle[n] = idle.get(n, 0.0) + (b - a - busy.within(a, b)) * 1e-9
    idle_s = (hi - lo - busy.total) * 1e-9
    idle["between phases"] = idle_s - sum(idle.values())
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy.total * 1e-9,
        program_s={k: program_seconds(tr, p, lo, hi)
                   for k, p in kernels.items()},
        top_ops=sorted(per_op.items(), key=lambda x: -x[1])[:top],
        idle_gaps=sorted(idle.items(), key=lambda x: -x[1])[:top])
