"""The readings that the correctness limits are set from, for one cell.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process: the cell's set-up and a short window at
its own load, then every number the check compares, for the program and
for the control (the float32 reference put in the program's place), and
what ``job_time_gap_s`` reads where jobs are timed without their per-SST
latency (that fault planted in the reference put in the program's place).
One JSON line per seed.  The benchmark's own runs do not run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> int:
    from lsmbench import catalog, cli, program
    from lsmbench.cell import Cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    wl = catalog.workload(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cli.enable_compile_cache()
    cli.device_info(wl.chips, require_tpu=True)
    if program.kernels_interpreted():
        raise SystemExit("the Pallas kernels would run interpreted")
    program.select_device_tier()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = Cell(wl.name, wl.config, wl.traffic, wl.kernels, seed)
        try:
            cell.setup(args.seconds)
            cell.window(args.seconds)
            sound = {n: v for n, v, _lim in cell.check()}
            control = {n: v for n, v, _lim in cell.check(control=True)}
            counts = cell.checked_counts()
            fault = _job_time_without_latency(cell)
        finally:
            cell.close()
        print(json.dumps({"seed": seed, "passes": len(cell.passes),
                          "failed": cell.failed, "error": cell.error,
                          "program": sound, "control": control,
                          "fault": {"job_time_gap_s": fault},
                          "checked": counts,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


def _job_time_without_latency(cell) -> float:
    """The largest gap of a kept job's device time, timed without one I/O
    latency per SST, from the device model's."""
    import numpy as np
    lat = cell.conf["device"]["io_latency"]
    gap = 0.0
    for k in cell.reservoir.kept:
        if k is not None and k.jobs["n_in"].size:
            ios = np.maximum(1, k.jobs["n_in"]) + np.maximum(1, k.jobs["n_out"])
            gap = max(gap, float(np.max(ios * lat)))
    return gap


if __name__ == "__main__":
    sys.exit(main())
