"""On-chip smoke test: the store's device path at the paper's byte scale.

Run on a machine with one TPU:

    python chip_smoke.py [--seed 7]

It drives one deployment through the store's normal entry points —
``RequestBatch`` -> ``LSMTree.apply_batch`` -> ``FleetEngine`` (structural
replay, temporal pass, Lindley, finalize) — twice in this one process:

* the **device run**: compaction merges on the ``merge_path`` kernel,
  manifest fence ranks on ``overlap_scan`` and departures on
  ``lindley_scan``, all compiled for the TPU;
* the **reference run**: the same stream on the numpy tier, with fresh
  uid namespaces so bloom seeding matches.

Deployment: the vLSM policy at the paper's byte scale
(``LSMConfig.vlsm_default(scale=1 << 26)``: 8 MB SSTs and memtable,
growth 8, phi 32, 200 B records; paper §5), ``DeviceModel.scaled(1.0)``,
one shard; a load of 2,000,000 keys in random order, a 10 s settle,
then 200,000 YCSB-A ops (50% GET / 50% update, zipfian 0.99) at 2,500
ops/s, the arrival scaffolding of ``db_bench.ycsb_a``.  Everything comes
from ``--seed``.

The two runs must agree exactly on the merged view, the per-op GET
accounting, a final batch of GETs, the Stats counters and the stalls;
departures must agree within the Lindley kernel's stated
``departure_tolerance``.  Earlier lines report device, sizes, compiles and
per-phase wall time (one un-repeated run; host clock, every phase ends
with its results on the host).  The last line is the JSON verdict.  Any
failed check, a non-TPU backend, or a kernel that would run interpreted
exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.bench_kv.db_bench import _load_settle_run  # noqa: E402
from repro.bench_kv.workloads import load_keys, make_run_a  # noqa: E402
from repro.core import (DeviceModel, LSMConfig, OpKind,  # noqa: E402
                        UidNamespace, level_index, merge)
from repro.core.fleet import FleetEngine  # noqa: E402
from repro.kernels.lindley_scan import departure_tolerance  # noqa: E402
from repro.kernels.lindley_scan.ops import lindley_batch_np  # noqa: E402

SCALE = 1 << 26            # paper byte scale: 8 MB SSTs and memtable
N_LOAD = 2_000_000
N_RUN = 200_000
RATE = 2_500.0             # db_bench.ycsb_a's run-phase rate (ops/s)
SETTLE_S = 10.0            # db_bench.ycsb_a's settle between load and run


@dataclass
class Stream:
    op_types: np.ndarray
    keys: np.ndarray
    arrivals: np.ndarray
    n_load: int


def make_stream(seed: int, n_load: int = N_LOAD, n_run: int = N_RUN
                ) -> Stream:
    """Load in random key order (YCSB's default hashed insert order, so
    flushes overlap and compactions merge), then the YCSB-A run."""
    pop = load_keys(n_load, seed)
    spec = make_run_a(pop, n_run, dist="zipfian", seed=seed + 14)
    load_arr, run_arr = _load_settle_run(pop.shape[0], n_run, RATE, SETTLE_S)
    return Stream(np.concatenate([np.zeros(pop.shape[0], np.uint8),
                                  spec.op_types]),
                  np.concatenate([pop, spec.keys]),
                  np.concatenate([load_arr, run_arr]), int(pop.shape[0]))


@dataclass
class Run:
    engine: FleetEngine
    result: object
    departures: np.ndarray
    probe: tuple[np.ndarray, np.ndarray, np.ndarray]
    phases: dict[str, float]


def run_tier(tier: str, cfg: LSMConfig, device: DeviceModel,
             stream: Stream, probe_keys: np.ndarray) -> Run:
    """One pass of the stream with merges, manifest ranks and Lindley on
    ``tier`` ("pallas" or "numpy"), then a GET batch on the final tree."""
    prev = merge.get_backend(), level_index.get_backend()
    merge.set_backend(tier)
    level_index.set_backend(tier)
    try:
        eng = FleetEngine(cfg, device, uids=UidNamespace())
        t0 = time.perf_counter()
        eng.prepare_structural(stream.op_types, stream.keys)
        t1 = time.perf_counter()
        pending = eng.temporal_pass(stream.arrivals)
        t2 = time.perf_counter()
        deps = lindley_batch_np([q[0] for q in pending.queues],
                                [q[1] for q in pending.queues],
                                backend=tier)
        t3 = time.perf_counter()
        res = eng.finalize(deps, pending=pending)
        t4 = time.perf_counter()
        probe = eng.trees[0].get_batch(probe_keys)
    finally:
        merge.set_backend(prev[0])
        level_index.set_backend(prev[1])
    return Run(eng, res, deps[0], probe,
               {"structural": t1 - t0, "temporal": t2 - t1,
                "lindley": t3 - t2, "finalize": t4 - t3})


def counters(run: Run) -> dict:
    st = run.result.stats
    return {"io_amp": st.io_amp, "write_amp": st.write_amp,
            "compactions": sum(st.compactions_per_level.values()),
            "chains": len(st.chains), "merged_keys": st.merged_keys,
            "n_stalls": run.result.n_stalls,
            "stall_total_s": run.result.stall_total}


def tails(run: Run, n_load: int) -> dict:
    lat = run.result.latency[n_load:]
    kinds = run.result.op_types[n_load:]
    out = {}
    for name, kind in (("get", OpKind.GET), ("put", OpKind.PUT)):
        sel = lat[kinds == kind]
        for q in (99, 99.9):
            out[f"p{q:g}_{name}_ms"] = float(np.percentile(sel, q)) * 1e3
    return out


def compare(dev: Run, ref: Run) -> dict:
    """Every exact-parity check, then the departure tolerance; raises
    SystemExit on the first difference."""
    checks = {
        "merged_view": dev.engine.trees[0].merged_view()
        == ref.engine.trees[0].merged_view(),
        "get_reads": np.array_equal(dev.result.get_reads,
                                    ref.result.get_reads),
        "get_probed": np.array_equal(dev.result.get_probed,
                                     ref.result.get_probed),
        "get_batch": all(np.array_equal(a, b)
                         for a, b in zip(dev.probe, ref.probe)),
        "counters": counters(dev) == counters(ref),
        "stall_events": dev.result.stall_events == ref.result.stall_events,
    }
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"device run differs from the numpy reference: "
                         f"{bad}; device {counters(dev)}, "
                         f"reference {counters(ref)}")
    d_dep = float(np.max(np.abs(dev.departures - ref.departures)))
    d_lat = float(np.max(np.abs(dev.result.latency - ref.result.latency)))
    bound = departure_tolerance(ref.departures.shape[0],
                                float(np.max(np.abs(ref.departures))))
    if not d_dep <= bound:
        raise SystemExit(f"departures differ by {d_dep!r} s, above the "
                         f"stated bound {bound!r} s")
    return {"max_abs_d_departure_s": d_dep, "max_abs_d_latency_s": d_lat,
            "bound_s": bound}


class CompileLog:
    """Counts XLA compiles and persistent-cache hits/misses through JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs


def kernel_shapes() -> dict[str, int]:
    """Distinct compiled shapes per kernel entry point."""
    from repro.kernels.lindley_scan import lindley_scan_call
    from repro.kernels.merge_path import merge_path_call
    from repro.kernels.overlap_scan import fence_rank_call
    return {"merge_path": merge_path_call._cache_size(),
            "fence_rank": fence_rank_call._cache_size(),
            "lindley_scan": lindley_scan_call._cache_size()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    from repro.kernels.platform import enable_compile_cache, interpret_mode
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    if dev0.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev0.platform!r}")
    if interpret_mode():
        raise SystemExit("the Pallas kernels would run interpreted")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = CompileLog()

    cfg = LSMConfig.vlsm_default(scale=SCALE)
    dm = DeviceModel.scaled(SCALE / (64 << 20))
    stream = make_stream(args.seed)
    rng = np.random.default_rng(args.seed + 1)
    probe_keys = np.concatenate([
        rng.choice(stream.keys, 50_000),
        load_keys(50_000, args.seed + 2)])      # mostly absent keys
    t0 = time.perf_counter()
    dev = run_tier("pallas", cfg, dm, stream, probe_keys)
    t_dev = time.perf_counter() - t0
    ref = run_tier("numpy", cfg, dm, stream, probe_keys)
    shapes = kernel_shapes()
    if not all(shapes.values()):
        raise SystemExit(f"a kernel never ran on the device: {shapes}")
    tree = dev.engine.trees[0]
    biggest = max((j.bytes_read for j in dev.result.job_log
                   if j.kind == "compact"), default=0) // cfg.kv_size
    print(f"sizes: keys_loaded={stream.n_load} run_ops={N_RUN} "
          f"ssts_per_level={[len(lv) for lv in tree.levels]} "
          f"chains={len(dev.result.stats.chains)} "
          f"largest_merge_keys={biggest}", flush=True)
    print(f"compiles: {log.compiles} in {log.compile_s:.3f} s "
          f"(persistent cache hits={log.hits} misses={log.misses}); "
          f"distinct kernel shapes {shapes}", flush=True)
    for name, run in (("device", dev), ("reference", ref)):
        phases = " ".join(f"{k}={v:.4f}s" for k, v in run.phases.items())
        print(f"phases[{name}] (one run): {phases}", flush=True)
    print(f"device run wall incl. compiles: {t_dev:.3f} s", flush=True)
    for name, run in (("device", dev), ("reference", ref)):
        print(f"tails[{name}]: {tails(run, stream.n_load)}", flush=True)
    parity = compare(dev, ref)
    print(f"parity: merged view, GET results, counters {counters(ref)} and "
          f"stalls equal; {parity}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
