"""In-program spans and counters at the store's layer boundaries.

The recorder is on exactly while a JAX profiler session runs
(``jax.profiler.trace`` / ``start_trace``): :func:`enabled` asks the
profiler's ``TraceMe.is_enabled()``, and is false whenever ``jax`` has not
been imported.  There is no other switch.

Off, :func:`span` returns one shared no-op context after that single
check and :func:`count` returns at once, so the store pays one profiler
query per boundary it crosses.

On, each span

* adds its duration (``time.perf_counter_ns``) to its name's total, and
  to the time the enclosing span's name spends in children, so
  :func:`self_seconds` gives each name's time less its children's;
* enters ``jax.profiler.TraceAnnotation(name)``, so the span lands in the
  profiler's trace on the device's clock and shows in TensorBoard or
  Perfetto beside the device's events.  The trace holds the nesting, and
  each span's start and end: it is the exporter.

Counters add into a dict.  Nothing is written to disk.  The recorder's
state is process-wide, like the profiler session that switches it; the
store is single-threaded and so is this recorder.  Span names are dotted
(``layer.what``); the tree the store writes is drawn in
``docs/architecture.md`` (Tracing).
"""

from __future__ import annotations

import contextlib
import functools
import sys
import time

_stack: list["_Open"] = []
_total_ns: dict[str, int] = {}
_child_ns: dict[str, int] = {}
_counters: dict[str, int] = {}
_is_on = None          # TraceMe.is_enabled, looked up once jax is loaded

#: what :func:`span` returns while the recorder is off
NOOP = contextlib.nullcontext()


def enabled() -> bool:
    """True while a JAX profiler session is running."""
    global _is_on
    if _is_on is None:
        if "jax" not in sys.modules:
            return False
        from jax._src.lib import _profiler
        _is_on = _profiler.TraceMe.is_enabled
    return _is_on()


class _Open:
    """A span while it runs."""

    __slots__ = ("name", "t0", "child_ns", "annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        import jax.profiler
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.child_ns = 0
        _stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        d = time.perf_counter_ns() - self.t0
        _stack.pop()
        _total_ns[self.name] = _total_ns.get(self.name, 0) + d
        _child_ns[self.name] = (_child_ns.get(self.name, 0)
                                + self.child_ns)
        if _stack:
            _stack[-1].child_ns += d
        self.annotation.__exit__(*exc)


def span(name: str):
    """A context that records one span while the profiler runs."""
    if not enabled():
        return NOOP
    return _Open(name)


def traced(name: str):
    """Decorator: each call of the function is one span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            if not enabled():
                return fn(*args, **kwargs)
            with _Open(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while the profiler runs."""
    if not enabled():
        return
    _counters[name] = _counters.get(name, 0) + int(n)


def total_seconds() -> dict[str, float]:
    """Each span name's summed duration."""
    return {k: v * 1e-9 for k, v in _total_ns.items()}


def self_seconds() -> dict[str, float]:
    """Each span name's summed duration less the part its direct
    children cover: the time spent in that layer's own code."""
    return {k: (v - _child_ns.get(k, 0)) * 1e-9
            for k, v in _total_ns.items()}


def counters() -> dict[str, int]:
    return dict(_counters)


def reset() -> None:
    """Forget every span and counter (call outside any open span)."""
    _total_ns.clear()
    _child_ns.clear()
    _counters.clear()
