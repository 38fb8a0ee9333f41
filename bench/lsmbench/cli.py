"""The command: one cell, one seed, one measured window, one result line.

Order of a run: find the cell's files; point JAX's persistent compilation
cache at its fixed directory; refuse anything but a TPU with the chips the
cell asks for, and kernels that would run interpreted; set up (the
stream and the warm-up passes), refusing a warm-up pass that runs past
the pass budget (``cell.pass_budget``: twice the window, at least 60 s);
measure the window (under the profiler with ``--trace 1``), where a pass
past its budget fails and ends the window; read the device's memory peak;
refuse a kernel the cell needs that never ran compiled; check what the
window produced against the reference; print the metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

from . import catalog, program, trace
from .cell import Cell, OverBudget, PassRecord

CACHE_DIR = catalog.BENCH / ".jax_cache"


class Refused(SystemExit):
    """Ends the run with no result line."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


@dataclass
class Readings:
    """What a metric reader is given (``bench/metrics/*.py``)."""

    passes: list[PassRecord]
    window_s: float
    setup_s: float
    compiles_in_window: int
    peaks: dict
    kv_size: int
    reduced: trace.Reduced | None = None


class CompileLog:
    """Counts the programs JAX compiles or loads from its persistent
    cache, through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _duration(self, name: str, _secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    where that is set, else at ``bench/.jax_cache`` in the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    dev0 = devs[0]
    if require_tpu and dev0.platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {dev0.platform!r}")
    if require_tpu and len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": dev0.platform, "kind": dev0.device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="One cell of the store's "
                                 "chip benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(argv, t_start: float, require_tpu: bool = True,
        wl: catalog.Workload | None = None) -> dict:
    """One run; returns the result object (the caller prints it).

    ``require_tpu=False`` and a given ``wl`` are for the harness's own
    tests, which drive a small cell on the CPU."""
    args = parse(argv)
    seed = args.seed % (1 << 64)
    wl = wl or catalog.workload(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # before JAX starts
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    device = device_info(wl.chips, require_tpu)
    print(f"device: {device}", file=sys.stderr)
    if require_tpu and program.kernels_interpreted():
        raise Refused("the Pallas kernels would run interpreted")
    peaks = catalog.peaks(device["kind"]) if require_tpu else {}
    print(f"device tier switched in: {program.select_device_tier()}",
          file=sys.stderr)
    log = CompileLog()

    cell = Cell(wl.name, wl.config, wl.traffic, wl.kernels, seed,
                annotate=bool(args.trace))
    try:
        cell.setup(args.seconds)
    except OverBudget as e:
        raise Refused(str(e)) from None
    compiles_before = log.count
    setup_s = time.perf_counter() - t_start
    print(f"setup: {setup_s:.4f} s, {compiles_before} programs compiled or "
          f"loaded", file=sys.stderr)
    for p in cell.warm:
        print(f"warm-up pass {p.index}: {p.wall_s:.4f} s, "
              f"{p.compaction_keys} keys read and written by compactions",
              file=sys.stderr)
    print(f"warm-up ladder: {cell.ladder_calls} calls, "
          f"{cell.ladder_s:.4f} s", file=sys.stderr)
    reduced = None
    if args.trace:
        with tempfile.TemporaryDirectory() as tmp:
            prof = trace.Profile(Path(tmp))
            with prof:
                w0, w1 = cell.window(args.seconds)
            tr = prof.read()
        reduced = trace.reduce(tr, {k.name: k.programs
                                    for k in wl.kernels.values()})
    else:
        w0, w1 = cell.window(args.seconds)
    compiles = log.count - compiles_before
    device["memory_peak_bytes"] = memory_peak()

    for k in wl.kernels.values():
        n = k.compiled_shapes()
        if n == 0:
            raise Refused(f"kernel {k.name} never ran compiled")
        print(f"kernel {k.name}: "
              f"{'entry gone' if n is None else f'{n} compiled shapes'}",
              file=sys.stderr)

    walls = [p.wall_s for p in cell.passes]
    print(f"window: {w1 - w0:.4f} s, {len(walls)} passes, "
          f"{sum(p.ops for p in cell.passes)} ops, "
          f"{compiles} programs compiled or loaded inside", file=sys.stderr)
    for p in cell.passes:
        print(f"pass {p.index}: {p.wall_s:.4f} s "
              + " ".join(f"{k}={v:.4f}" for k, v in p.phases.items()),
              file=sys.stderr)

    checks = cell.check()
    print(f"checked: {cell.checked_counts()}", file=sys.stderr)
    cell.close()
    correct = cell.failed == 0 and bool(cell.passes) and all(
        v <= lim for _n, v, lim in checks)

    readings = Readings(cell.passes, w1 - w0, setup_s, compiles, peaks,
                        cell.cfg.kv_size, reduced)
    metrics = {}
    for m in (wl.per_layer if args.trace else wl.end_to_end):
        value = catalog.reader(m["name"])(readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": correct, "attempted": len(cell.passes) + cell.failed,
           "failed": cell.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in reduced.top_ops],
                            "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    if cell.error:
        print(f"failed: {cell.error}", file=sys.stderr)
    for n, v, lim in checks:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr)
    out["check"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    out = run(argv, t_start)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
