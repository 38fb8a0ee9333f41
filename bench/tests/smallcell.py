"""A cell of the benchmark cut to a size the CPU's Pallas interpreter
runs in a second or two per pass, for the harness's own tests."""

from __future__ import annotations

import dataclasses
import json
import time

from lsmbench import catalog, cli


def small(name: str, records: int = 12_000, ops: int = 2_000,
          sst_bytes: int = 1 << 17, rate: float | None = None,
          fields: dict | None = None) -> catalog.Workload:
    """``name`` with ``records`` loaded, ``ops`` in the run phase and SSTs
    and memtables of ``sst_bytes``, the configuration's byte sizes scaled
    alike; ``rate`` replaces the run phase's rate, and ``fields`` other
    fields of the traffic."""
    config, mix = name.split(".", 1)
    wl = catalog.assemble(name, config, mix)
    conf = json.loads(json.dumps(wl.config))
    store = conf["store"]
    store["scale_bytes"] = (store["scale_bytes"] * sst_bytes
                            // store["sst_size"])
    store["memtable_size"] = store["sst_size"] = sst_bytes
    conf["record_count"] = records
    traffic = dataclasses.replace(wl.traffic, operation_count=ops,
                                  **(fields or {}))
    if rate is not None:
        traffic = dataclasses.replace(traffic, run_rate_ops_s=rate)
    return dataclasses.replace(wl, config=conf, traffic=traffic)


def run_small(name: str, seed: int = 7_000_000_001, seconds: float = 2.0,
              trace: int = 0, **kw) -> dict:
    """The whole run but the look for a chip, on a small cell.  A run
    that raises (a fault can break the store before the window) is
    reported as not correct, and so is one that the harness refuses (it
    prints no result)."""
    try:
        return cli.run(["--workload", name, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)],
                       time.perf_counter(), require_tpu=False,
                       wl=small(name, **kw))
    except (Exception, SystemExit) as e:  # noqa: BLE001 (not correct)
        return {"correct": False, "error": f"{type(e).__name__}: {e}"}
