"""Finds everything a cell is made of, by the names in ``BENCHMARK.json``.

* a configuration: ``bench/configs/<config>.json``;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a kernel: ``bench/kernels/<kernel>.json``;
* the kernels of a cell: those its traffic mix names, then, without
  repeats, those ``bench/cells/<cell>.json`` names under ``"kernels"``,
  where that file exists: a kernel that only one cell's program uses (a
  cell entry of ``BENCHMARK.json`` takes no keys beyond its contract's);
* a metric: ``bench/metrics/<metric>.py``, whose ``read(readings)``
  returns the number, or None where it finds nothing to read;
* a kernel's per-call reference: ``bench/references/<reference>.py``,
  whose ``differs(args, out)`` says whether one call's answer differs
  from the reference's;
* chip peaks: ``bench/peaks.json``, keyed by JAX's ``device_kind``.

Adding a cell, a mix, a kernel or a metric adds files and entries; no
file that is there changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

from .program import Kernel
from .streams import Traffic

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Workload:
    name: str
    chips: int
    config: dict
    traffic: Traffic
    kernels: dict[str, Kernel]
    end_to_end: list[dict]
    per_layer: list[dict]


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str, e2e_of_cell: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_of_cell
    return True


def _bench(root: Path) -> Path:
    return root / BENCH.relative_to(ROOT)


def traffic(name: str, root: Path = ROOT) -> Traffic:
    return Traffic.from_json(
        name, _json(_bench(root) / "traffic" / f"{name}.json"))


def _kernel_names(cell: str, mix: Traffic, root: Path = ROOT
                 ) -> tuple[str, ...]:
    """The kernels of the cell ``cell`` on the mix ``mix``: the mix's,
    then those its cell file adds, each once."""
    path = _bench(root) / "cells" / f"{cell}.json"
    extra = _json(path)["kernels"] if path.exists() else []
    return tuple(dict.fromkeys([*mix.kernels, *extra]))


def workload(name: str, root: Path = ROOT) -> Workload:
    """The cell ``name`` of ``BENCHMARK.json`` with all its parts."""
    cells = {w["name"]: w for w in benchmark(root)["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    w = cells[name]
    return assemble(name, w["config"], w["traffic"], int(w["chips"]), root)


def assemble(name: str, config_name: str, traffic_name: str, chips: int = 1,
             root: Path = ROOT) -> Workload:
    """A cell from a configuration and a traffic mix by their names; the
    kernels are those of the mix and of the cell file of ``name``, the
    metrics those ``BENCHMARK.json`` gives the cell ``name``."""
    bm = benchmark(root)
    confs = {c["name"]: c for c in bm["configs"]}
    conf = _json(root / confs[config_name]["file"])
    mix = traffic(traffic_name, root)
    kernels = {k: Kernel.load(_bench(root), k)
               for k in _kernel_names(name, mix, root)}
    e2e = [m for m in bm["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _reports(m, name, e2e_names)]
    return Workload(name, chips, conf, mix, kernels, e2e, per_layer)


def peaks(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _module("metrics", metric).read


def call_check(reference: str):
    """The ``differs`` function of ``bench/references/<reference>.py``."""
    return _module("references", reference).differs
