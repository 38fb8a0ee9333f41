"""Every place where the benchmark touches the store under test.

The benchmark drives the store through its public phases
(``FleetEngine.prepare_structural`` -> ``temporal_pass`` ->
``lindley_batch_np`` -> ``finalize``) and reads back the merged view, a
GET batch, a SCAN batch, the result's job log and per-op latencies.  It
asks for the device tier through the program's own switches where they
still exist (``set_backend`` and ``backend=``); where a later version of
the program has removed them, the platform's choice stands and nothing
here needs to change.

Kernels are named by the files under ``bench/kernels/``: each gives the
jitted entry whose compiled-shape count proves that the kernel ran
compiled, the host entry that the correctness check taps, the name of
its program in the device trace, and, where the sizes of its calls vary
from pass to pass, how to make calls of any size for the warm-up.
"""

from __future__ import annotations

import importlib
import inspect
import json
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np


def _resolve(path: str):
    """``"package.module:attr"`` -> (module, attr name, object or None)."""
    mod_name, attr = path.split(":")
    try:
        mod = importlib.import_module(mod_name)
    except ImportError:
        return None, attr, None
    return mod, attr, getattr(mod, attr, None)


@dataclass
class Kernel:
    """One device kernel of the store, as ``bench/kernels/<name>.json``
    describes it."""

    name: str
    entry: str        # jitted callable: its cache size counts compiled shapes
    host_entry: str   # numpy-in, numpy-out wrapper the check taps
    reference: str    # ``bench/references/<reference>.py`` checks a call
    programs: tuple[str, ...]  # program names of its events in the trace
    # ``{"sizes_of_args": [i, ...], "args": ["sorted <axis>" | "count
    # <axis>", ...]}``: the arguments whose lengths are a call's sizes,
    # and how to make each argument for given sizes; None where a call's
    # sizes do not vary
    warm: dict | None = None

    @staticmethod
    def load(bench_dir: Path, name: str) -> "Kernel":
        d = json.loads((bench_dir / "kernels" / f"{name}.json").read_text())
        return Kernel(name, d["entry"], d["host_entry"], d["reference"],
                      tuple(d["programs"]), d.get("warm"))

    def sizes(self, args) -> tuple[int, ...]:
        return tuple(len(args[i]) for i in self.warm["sizes_of_args"])

    def make_args(self, sizes: tuple[int, ...], pool: np.ndarray) -> list:
        """Arguments of the given sizes: ``sorted`` takes a prefix of the
        sorted ``pool``, ``count`` counts up from 0 (int64)."""
        out = []
        for spec in self.warm["args"]:
            kind, axis = spec.split()
            n = sizes[int(axis)]
            out.append(pool[:n] if kind == "sorted"
                       else np.arange(n, dtype=np.int64))
        return out

    def compiled_shapes(self) -> int | None:
        """Distinct shapes compiled so far; None where the program no
        longer has this entry."""
        _mod, _attr, fn = _resolve(self.entry)
        size = getattr(fn, "_cache_size", None)
        return None if size is None else int(size())


def select_device_tier() -> list[str]:
    """Switch the program's merge and manifest onto the device tier where
    it still has module switches; returns what was switched."""
    done = []
    for mod_name in ("repro.core.merge", "repro.core.level_index"):
        mod = importlib.import_module(mod_name)
        if hasattr(mod, "set_backend"):
            mod.set_backend("pallas")
            done.append(mod_name)
    return done


def kernels_interpreted() -> bool:
    """True where the program would run its Pallas kernels interpreted."""
    _mod, _attr, fn = _resolve("repro.kernels.platform:interpret_mode")
    return bool(fn()) if fn is not None else False


def lindley(services: list[np.ndarray],
            arrivals: list[np.ndarray]) -> list[np.ndarray]:
    """The program's batched departure scan on the device tier."""
    _mod, _attr, fn = _resolve(
        "repro.kernels.lindley_scan.ops:lindley_batch_np")
    if "backend" in inspect.signature(fn).parameters:
        return fn(services, arrivals, backend="pallas")
    return fn(services, arrivals)


def new_engine(cfg, device):
    from repro.core import FleetEngine, UidNamespace
    return FleetEngine(cfg, device, uids=UidNamespace())


def scan(tree, starts: np.ndarray, lengths: np.ndarray
         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A batch of forward range scans on one tree, flattened:
    ``(keys, seqs, offsets)``, scan ``i`` owning
    ``offsets[i]:offsets[i + 1]``."""
    res = tree.scan_batch(np.asarray(starts, np.int64),
                          np.asarray(lengths, np.int32))
    return (np.asarray(res.scan_keys, np.int64),
            np.asarray(res.scan_seqs, np.int64),
            np.asarray(res.scan_offsets, np.int64))


def build_config(store: dict):
    """The store configuration a config file states: the policy's default
    at the stated byte scale, with every stated field applied."""
    from repro.core import get_policy
    cfg = get_policy(store["policy"]).default_config(
        scale=int(store["scale_bytes"]))
    fields = {f for f in inspect.signature(type(cfg)).parameters}
    stated = {k: v for k, v in store.items() if k in fields}
    return cfg.with_(**stated)


def build_device(device: dict):
    from repro.core import DeviceModel
    return DeviceModel(**device)


@dataclass
class Tap:
    """Records a seeded sample of one host entry's calls while ``armed``:
    the arguments and the answer, for the check after the window; and,
    while ``sizes`` is a set, the sizes of every call that reaches the
    device (none of its sizes is 0)."""

    kernel: Kernel
    share: float
    rng: np.random.Generator
    armed: bool = False
    calls: list = field(default_factory=list)
    seen: int = 0
    sizes: set | None = None

    def install(self) -> bool:
        mod, attr, fn = _resolve(self.kernel.host_entry)
        if fn is None:
            return False
        self.fn = fn

        def tapped(*args):
            out = fn(*args)
            if self.sizes is not None and all(self.kernel.sizes(args)):
                self.sizes.add(self.kernel.sizes(args))
            if self.armed:
                self.seen += 1
                if self.rng.random() < self.share:
                    self.calls.append((args, out))
            return out

        setattr(mod, attr, tapped)
        self._restore = (mod, attr, fn)
        return True

    def remove(self) -> None:
        mod, attr, fn = self._restore
        setattr(mod, attr, fn)


def job_arrays(job_log) -> dict:
    """One pass's job log as arrays: kind, times, bytes and SST counts of
    each job, and its dependency edges as index pairs (``dep`` must finish
    before ``dep_of`` starts)."""
    at = {id(j): i for i, j in enumerate(job_log)}
    dep, dep_of = [], []
    for i, j in enumerate(job_log):
        for d in [*j.deps, *([j.parent_job] if j.parent_job else [])]:
            if id(d) in at:
                dep.append(at[id(d)])
                dep_of.append(i)

    def col(name, dtype):
        return np.array([getattr(j, name) for j in job_log], dtype)

    return {"compact": np.array([j.kind == "compact" for j in job_log], bool),
            "t_start": col("t_start", np.float64),
            "t_finish": col("t_finish", np.float64),
            "bytes_read": col("bytes_read", np.int64),
            "bytes_written": col("bytes_written", np.int64),
            "n_in": col("n_in_ssts", np.int64),
            "n_out": col("n_out_ssts", np.int64),
            "dep": np.array(dep, np.int64), "dep_of": np.array(dep_of, np.int64)}


def stall_arrays(stall_events) -> tuple[np.ndarray, np.ndarray]:
    """One pass's write stalls: the op each waited at, and how long."""
    ops = np.array([i for i, _ in stall_events], np.int64)
    return ops, np.array([d for _, d in stall_events], np.float64)


def compaction_bytes(job_log, kv_size: int) -> tuple[int, int]:
    """(keys read, keys written) by the compactions of one job log."""
    read = written = 0
    for job in job_log:
        if job.kind == "compact":
            read += job.bytes_read
            written += job.bytes_written
    return read // kv_size, written // kv_size
