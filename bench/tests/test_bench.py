"""The harness's own arithmetic and plumbing, on the CPU."""

from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from lsmbench import catalog, cli, program, streams, trace
from lsmbench.cell import Cell, PassRecord

ROOT = catalog.ROOT
DATA = Path(__file__).resolve().parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# ------------------------------------------------------------ the catalog
def test_every_cell_resolves_by_name():
    bm = catalog.benchmark()
    for w in bm["workloads"]:
        wl = catalog.workload(w["name"])
        assert wl.config["store"]["policy"]
        assert wl.traffic.kernels
        names = {m["name"] for m in wl.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert wl.per_layer
        for k in wl.kernels.values():
            assert k.entry and k.programs
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(catalog.reader(m["name"]))
        for cell in m.get("workloads", ()):
            catalog.workload(cell)
    for c in bm["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(conf["reduced"])


def test_a_missing_cell_is_an_error():
    with pytest.raises(KeyError):
        catalog.workload("no-such.cell")


def test_peaks_know_the_v5e_and_refuse_others():
    assert catalog.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        catalog.peaks("TPU v9 imaginary")


def test_configs_build_the_stated_store():
    for c in catalog.benchmark()["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        cfg = program.build_config(conf["store"])
        for key, value in conf["store"].items():
            if hasattr(cfg, key):
                assert getattr(cfg, key) == value, key


# ------------------------------------------------------------ the traffic
def test_copied_generators_match_the_program():
    from repro.bench_kv import db_bench, workloads
    seed = 3_000_000_007
    pop = streams.load_keys(5_000, seed)
    np.testing.assert_array_equal(pop, workloads.load_keys(5_000, seed))
    ops, keys = streams.read_update_mix(pop, 3_000, 0.5, 0.99, seed + 14)
    spec = workloads.make_run_a(pop, 3_000, dist="zipfian", seed=seed + 14)
    np.testing.assert_array_equal(ops, spec.op_types)
    np.testing.assert_array_equal(keys, spec.keys)
    load, run = db_bench._load_settle_run(5_000, 3_000, 2_500.0, 10.0)
    np.testing.assert_array_equal(
        streams.load_settle_run(5_000, 3_000, 1e6, 2_500.0, 10.0),
        np.concatenate([load, run]))


def test_pass_inputs_are_new_every_pass():
    maps = [streams.key_map(2**33 + 5, i) for i in range(-1, 100)]
    assert len(set(maps)) == 101
    assert all(a % 2 == 1 and 0 < a < streams.KEYSPACE
               and 0 <= b < streams.KEYSPACE for a, b in maps)
    assert streams.key_map(2**33 + 5, 7) == maps[8]


def test_key_map_is_a_bijection_that_reorders():
    r = np.random.default_rng(3)
    keys = np.concatenate([r.integers(0, streams.KEYSPACE, 5_000),
                           [0, streams.KEYSPACE - 1]]).astype(np.int64)
    base = streams.Stream(np.zeros(keys.size, np.uint8), keys,
                          np.zeros(keys.size), keys.size)
    a, b = streams.key_map(2**40 + 1, 3)
    moved = base.mapped((a, b))
    assert moved.keys.min() >= 0 and moved.keys.max() < streams.KEYSPACE
    want = [(a * int(k) + b) % streams.KEYSPACE for k in keys[:50]]
    assert moved.keys[:50].tolist() == want
    assert np.unique(moved.keys).size == np.unique(keys).size
    assert not np.array_equal(np.argsort(moved.keys), np.argsort(keys))


# ---------------------------------------------------------- the reference
def test_reference_latest_writes_and_gets():
    from lsmbench import reference
    op_types = np.array([0, 0, 1, 0, 0], np.uint8)
    keys = np.array([7, 3, 7, 7, 2], np.int64)
    uk, useq = reference.latest_writes(op_types, keys)
    assert uk.tolist() == [2, 3, 7] and useq.tolist() == [3, 1, 2]
    got = reference.get_answers((uk, useq), np.array([7, 4, 2, 99]))
    assert got.tolist() == [2, -1, 3, -1]


def test_reference_departures_follow_the_recursion():
    from lsmbench import reference
    r = np.random.default_rng(1)
    s, a = r.random(50) * 0.1, np.sort(r.random(50)) * 3
    d, want = -np.inf, []
    for si, ai in zip(s, a):
        d = max(ai, d) + si
        want.append(d)
    np.testing.assert_allclose(reference.departures(s, a), want, rtol=0,
                               atol=1e-12)


def _jobs(**kw):
    base = {"compact": np.array([True, True, False]),
            "t_start": np.array([0.0, 0.5, 0.2]),
            "t_finish": np.array([1.0, 2.0, 0.3]),
            "bytes_read": np.array([3_500, 7_000, 0]),
            "bytes_written": np.array([2_000, 0, 4_000]),
            "n_in": np.array([2, 1, 0]), "n_out": np.array([1, 0, 1]),
            "dep": np.array([0]), "dep_of": np.array([2])}
    base.update(kw)
    return base


DEVICE = {"read_bw": 3.5e3, "write_bw": 2e3, "io_latency": 0.25,
          "block_size": 35, "compaction_slots": 3}


def test_reference_device_model_of_jobs():
    from lsmbench import reference
    np.testing.assert_allclose(reference.job_seconds(_jobs(), DEVICE),
                               [1 + 0.5 + 1 + 0.25, 2 + 0.25 + 0.25,
                                0.25 + 2 + 0.25])
    assert reference.most_at_once(np.array([0.0, 1.0, 0.5]),
                                  np.array([1.0, 2.0, 0.9])) == 2
    # two compactions in two slots; the flush starts before its dep ends
    assert reference.schedule_violations(_jobs(), DEVICE) == 1
    tight = dict(DEVICE, compaction_slots=2)
    assert reference.schedule_violations(
        _jobs(t_start=np.array([0.0, 0.5, 1.2]),
              t_finish=np.array([1.0, 2.0, 1.3])), tight) == 1


def test_reference_services_and_fill_ops():
    from lsmbench import reference
    op_types = np.array([0, 1, 0, 1, 0, 0], np.uint8)
    assert reference.fill_ops(op_types, 2).tolist() == [2, 5]
    arrivals = np.array([0.0, 0.6, 0.7, 2.5, 3.0, 3.1])
    svc = reference.services(op_types, arrivals, np.array([0, 2, 0, 1, 0, 0]),
                             _jobs(), np.array([5]), np.array([0.5]), DEVICE,
                             {"put_s": 0.1, "get_s": 0.2, "busy_alpha": 0.5})
    block = 0.25 + 0.01
    # the GET at 0.6 overlaps both compactions, the one at 2.5 none
    np.testing.assert_allclose(svc, [0.1, 0.2 + 2 * block * (1 + 0.5 * 2),
                                     0.1, 0.2 + block, 0.1, 0.6])


# ------------------------------------------------------- the byte counts
def test_compaction_keys_from_a_job_log():
    jobs = [SimpleNamespace(kind="compact", bytes_read=2000, bytes_written=1800),
            SimpleNamespace(kind="flush", bytes_read=0, bytes_written=4000),
            SimpleNamespace(kind="compact", bytes_read=600, bytes_written=600)]
    assert program.compaction_bytes(jobs, 200) == (13, 12)


def _readings(passes, reduced=None):
    return cli.Readings(passes, window_s=2.0, setup_s=1.0,
                        compiles_in_window=0,
                        peaks={"hbm_bytes_per_s": 819e9}, kv_size=200,
                        reduced=reduced)


def test_rooflines_from_counts_and_device_time():
    passes = [PassRecord(1, 1_000_000, 0.0, 1.0, {}, 25_000_000),
              PassRecord(2, 1_000_000, 1.0, 1.0, {}, 25_000_000)]
    red = trace.Reduced(window_s=2.0, busy_s=0.5,
                        program_s={"merge_path": 0.1, "lindley_scan": 0.01,
                                   "fence_rank": 0.0},
                        top_ops=[], idle_gaps=[])
    r = _readings(passes, red)
    merge = catalog.reader("merge_path_roofline")(r)
    assert merge == pytest.approx(100 * 50e6 * 16 / 819e9 / 0.1)
    lindley = catalog.reader("lindley_scan_roofline")(r)
    assert lindley == pytest.approx(100 * 2e6 * 24 / 819e9 / 0.01)
    assert catalog.reader("fence_rank_device_s_per_pass")(r) is None
    assert catalog.reader("device_idle_share")(r) == pytest.approx(75.0)
    assert catalog.reader("ops_per_s")(r) == pytest.approx(1e6)
    # without a trace the device metrics find nothing to read
    assert catalog.reader("merge_path_roofline")(_readings(passes)) is None


# ---------------------------------------------------------- the window
def test_window_rule(monkeypatch):
    """Passes start while under the window's length, and the window ends
    when the last pass that started ends."""
    starts = []

    def fake_pass(self, i, keep=True):
        t0 = time.perf_counter()
        starts.append(t0)
        time.sleep(0.03)
        return PassRecord(i, 10, t0, time.perf_counter() - t0, {}, 0)

    monkeypatch.setattr(Cell, "run_pass", fake_pass)
    c = Cell.__new__(Cell)
    c.annotate, c.passes, c.failed, c.error = False, [], 0, ""
    w0, w1 = c.window(0.1)
    assert all(s - w0 < 0.1 for s in starts)
    assert len(starts) >= 3
    last = c.passes[-1]
    assert w1 >= last.start + last.wall_s
    assert last.start + last.wall_s - w0 >= 0.1


def test_warm_ladder_covers_the_neighbours_of_seen_sizes():
    from lsmbench import cell as cell_mod
    calls = []
    kernel = program.Kernel("k", "", "", "", (), {
        "sizes_of_args": [0, 2], "args": ["sorted 0", "count 0",
                                          "sorted 1", "count 1"]})
    tap = SimpleNamespace(kernel=kernel, sizes={(3000, 40), (70_000, 64)},
                          fn=lambda *a: calls.append(tuple(map(len, a))))
    c = Cell.__new__(Cell)
    c.seed, c.taps = 5, {"k": tap}
    assert c._warm_ladder() == len(calls) == 8 * 3
    firsts = sorted({a for a, _, _, _ in calls})
    seconds = sorted({b for _, _, b, _ in calls})
    assert firsts == [2048 << i for i in range(8)]      # 2048 .. 262144
    assert seconds == [32, 64, 128]
    assert all(n == a for a, n, _, _ in calls) and tap.sizes is None
    assert cell_mod._pow2(0) == 1 and cell_mod._pow2(1025) == 2048


def test_kept_passes_are_seeded_among_the_first():
    from lsmbench.cell import Reservoir
    picks = {tuple(Reservoir(2, 4, seed).chosen) for seed in range(40)}
    assert len(picks) > 1
    assert all(len(p) == 2 and set(p) <= {1, 2, 3, 4} for p in picks)
    again = Reservoir(2, 4, 2**40 + 3)
    assert again.chosen == Reservoir(2, 4, 2**40 + 3).chosen
    assert [again.slot(i) for i in range(1, 6)].count(None) == 3


# --------------------------------------------------------- the reduction
def _synthetic() -> trace.Trace:
    ms = 1e6
    return trace.Trace(
        programs=[("jit_merge_path_call(3)", 10 * ms, 5 * ms),
                  ("jit_lindley_scan_call", 40 * ms, 10 * ms),
                  ("jit_merge_path_call(9)", 95 * ms, 10 * ms)],
        ops=[("fusion", 10 * ms, 3 * ms), ("merge_kernel", 12 * ms, 3 * ms),
             ("lindley", 40 * ms, 10 * ms), ("fusion", 95 * ms, 10 * ms)],
        spans=[("window", 0.0, 100 * ms),
               ("structural", 0.0, 30 * ms), ("lindley", 30 * ms, 60 * ms)])


def test_reduction_on_a_synthetic_trace():
    red = trace.reduce(_synthetic(), {"merge_path": ("jit_merge_path_call",),
                                      "lindley_scan": ("jit_lindley_scan_call",)})
    assert red.window_s == pytest.approx(0.1)
    # busy: [10, 15] + [40, 50] + [95, 100] (clipped to the window)
    assert red.busy_s == pytest.approx(0.020)
    assert red.idle_share == pytest.approx(0.8)
    # the third merge program ends after the window and is not counted
    assert red.program_s["merge_path"] == pytest.approx(0.005)
    assert red.program_s["lindley_scan"] == pytest.approx(0.010)
    assert red.top_ops[0] == ("lindley", pytest.approx(0.010))
    gaps = dict(red.idle_gaps)
    # structural [0,30] holds busy [10,15]; lindley [30,90] holds [40,50];
    # [90,100] holds [95,100] and no phase
    assert gaps == {"structural": pytest.approx(0.025),
                    "lindley": pytest.approx(0.050),
                    "between phases": pytest.approx(0.005)}


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    busy = trace.Busy([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert busy.total == 6
    assert busy.within(0, 2) == 1 and busy.within(3.5, 6) == 1.5


@pytest.mark.skipif(not (DATA / "trace_small.json").exists(),
                    reason="no recorded chip trace")
def test_reduction_on_the_recorded_trace():
    tr = trace.Trace.from_json(json.loads(
        (DATA / "trace_small.json").read_text()))
    kernels = {k: catalog.workload("vlsm-8m.ycsb-a.replay").kernels[k].programs
               for k in ("merge_path", "fence_rank", "lindley_scan")}
    red = trace.reduce(tr, kernels)
    expect = json.loads((DATA / "trace_small_reduced.json").read_text())
    assert red.window_s == pytest.approx(expect["window_s"], rel=1e-12)
    assert red.busy_s == pytest.approx(expect["busy_s"], rel=1e-12)
    for k, v in expect["program_s"].items():
        assert red.program_s[k] == pytest.approx(v, rel=1e-12)
    assert 0 < red.busy_s < red.window_s
    idle = dict(red.idle_gaps)
    assert set(idle) >= {"structural", "temporal", "lindley", "finalize"}
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    for k, v in expect["idle_s"].items():
        assert idle[k] == pytest.approx(v, rel=1e-9)


# ------------------------------------------------------------- refusals
def test_run_refuses_a_cpu_backend_in_process():
    with pytest.raises(SystemExit, match="no TPU"):
        cli.run(["--workload", "vlsm-8m.ycsb-a.replay", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], time.perf_counter())


def test_command_off_a_tpu_exits_without_a_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "vlsm-8m.ycsb-a.replay", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                          "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "vlsm-8m.ycsb-a.replay", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                          "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert proc.returncode != 0
    assert not proc.stdout.strip()
