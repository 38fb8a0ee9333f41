"""The harness's own arithmetic and plumbing, on the CPU."""

from __future__ import annotations

import dataclasses
import hashlib
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
    ops, keys, lens = streams.ycsb_mix(pop, 3_000, 0.5, 0.0, 0.0, 1000,
                                       0.99, seed + 14)
    spec = workloads.make_run_a(pop, 3_000, dist="zipfian", seed=seed + 14)
    np.testing.assert_array_equal(ops, spec.op_types)
    np.testing.assert_array_equal(keys, spec.keys)
    assert not lens.any()
    load, run = db_bench._load_settle_run(5_000, 3_000, 2_500.0, 10.0)
    np.testing.assert_array_equal(
        streams.load_settle_run(5_000, 3_000, 1e6, 2_500.0, 10.0),
        np.concatenate([load, run]))


def test_copied_scan_mix_matches_the_program():
    from repro.bench_kv import workloads
    seed = 3_000_000_011
    pop = streams.load_keys(5_000, seed)
    ops, keys, lens = streams.ycsb_mix(pop, 3_000, 0.0, 0.05, 0.95, 100,
                                       0.99, seed + 14)
    spec = workloads.make_run_e(pop, 3_000, dist="zipfian", seed=seed + 14,
                                max_scan_len=100)
    np.testing.assert_array_equal(ops, spec.op_types)
    np.testing.assert_array_equal(keys, spec.keys)
    np.testing.assert_array_equal(lens, spec.scan_lens)
    assert (ops == streams.SCAN).sum() > 2_700 and lens.max() == 100


def _digest(*arrays) -> str:
    h = hashlib.blake2b(digest_size=16)
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_ycsb_a_stream_is_pinned():
    """The read/update stream of ``ycsb-a.replay`` at a small size, as
    the harness made it before SCANs and INSERTs came in."""
    mix = dataclasses.replace(catalog.traffic("ycsb-a.replay"),
                              operation_count=3_000)
    s = mix.base_stream(5_000, 3_000_000_007)
    assert s.op_types.dtype == np.uint8 and s.keys.dtype == np.int64
    assert _digest(s.op_types, s.keys, s.arrivals) \
        == "053363c022f6abd7a99a8b8a7cacc721"
    assert s.scan_lens is None and not mix.scans and mix.probe_scans == 0


def test_ycsb_e_traffic_file():
    d = json.loads((ROOT / "bench" / "traffic" / "ycsb-e.replay.json")
                   .read_text())
    a = json.loads((ROOT / "bench" / "traffic" / "ycsb-a.replay.json")
                   .read_text())
    mix = catalog.traffic("ycsb-e.replay")
    assert (mix.operation_count, mix.read_proportion, mix.scan_proportion,
            mix.insert_proportion, mix.zipfian_theta, mix.max_scan_length,
            mix.scan_length_distribution) == (200_000, 0.0, 0.95, 0.05, 0.99,
                                              100, "uniform")
    assert (mix.load_rate_ops_s, mix.settle_s, mix.run_rate_ops_s) \
        == (1e6, 10.0, 2_500.0)
    assert mix.kernels == ("merge_path", "fence_rank", "lindley_scan")
    assert mix.probe_scans == 4_096
    assert {k: v for k, v in d["check"].items() if k != "probe_scans"} \
        == a["check"]
    s = dataclasses.replace(mix, operation_count=2_000).base_stream(3_000, 9)
    assert s.scan_lens.shape == s.op_types.shape
    assert not s.scan_lens[:3_000].any()
    run = s.op_types[3_000:]
    assert set(np.unique(run)) == {streams.PUT, streams.SCAN}
    assert (s.scan_lens[3_000:][run == streams.SCAN] >= 1).all()
    moved = s.mapped(streams.key_map(9, 1))
    assert moved.scan_lens is s.scan_lens


def test_traffic_refuses_what_it_cannot_generate():
    d = json.loads((ROOT / "bench" / "traffic" / "ycsb-e.replay.json")
                   .read_text())
    with pytest.raises(ValueError, match="uniform"):
        streams.Traffic.from_json("x", dict(d, scan_length_distribution=
                                            "zipfian"))
    with pytest.raises(ValueError, match="over 1"):
        streams.Traffic.from_json("x", dict(d, read_proportion=0.5))


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


def _scans_by_loop(op_types, keys, lens, kpm):
    """SCANs the plain way: writes land in a dict as they come, reads wait
    for the end of their memtable window (every ``kpm``-th write)."""
    store, seq, writes, waiting, counts = {}, 0, 0, [], []

    def resolve():
        for start, length in waiting:
            counts.append(len(sorted(k for k in store if k >= start)[:length]))
        waiting.clear()

    for op, key, length in zip(op_types.tolist(), keys.tolist(),
                               lens.tolist()):
        if op == 0:
            store[key], seq, writes = seq, seq + 1, writes + 1
            if writes % kpm == 0:
                resolve()
        elif op == 3:
            waiting.append((key, length))
    resolve()
    return store, counts


def test_reference_scans_against_a_loop():
    from lsmbench import reference
    # windows of 3 writes: ops 0-3, 4-8, then 9-11 to the stream's end;
    # key 10 is overwritten, 50 is inserted after SCANs of its window and
    # 40 after one of an earlier window, 99 starts past every key
    op_types = np.array([0, 3, 0, 0, 3, 0, 3, 0, 0, 3, 3, 0], np.uint8)
    keys = np.array([10, 12, 20, 30, 15, 25, 99, 10, 40, 5, 26, 50],
                    np.int64)
    lens = np.array([0, 3, 0, 0, 5, 0, 3, 0, 0, 2, 10, 0], np.int32)
    store, want = _scans_by_loop(op_types, keys, lens, 3)
    got = reference.scan_delivered(op_types, keys, lens, 3)
    assert got.tolist() == want == [2, 4, 0, 2, 3]
    written = reference.latest_writes(op_types, keys)
    starts = np.array([0, 26, 41, 99, 10, 51], np.int64)
    lengths = np.array([2, 10, 1, 4, 3, 1])
    k, sq, off = reference.scan_answers(written, starts, lengths)
    for i, (start, length) in enumerate(zip(starts, lengths)):
        top = sorted(x for x in store if x >= start)[:length]
        assert k[off[i]:off[i + 1]].tolist() == top
        assert sq[off[i]:off[i + 1]].tolist() == [store[x] for x in top]
    assert np.diff(off).tolist() == [2, 3, 1, 0, 3, 0]
    assert store[10] == 4


def test_reference_scan_services_match_the_program():
    """The SCAN terms of the reference's service, against the service the
    store puts in its queue, on a small YCSB-E stream whose run phase
    arrives while the load's compactions still run."""
    from lsmbench import reference
    from smallcell import small
    wl = small("vlsm-8m.ycsb-e.replay", records=6_000, ops=1_000)
    mix = dataclasses.replace(wl.traffic, settle_s=0.0, run_rate_ops_s=1e6)
    conf, store = wl.config, wl.config["store"]
    s = mix.base_stream(conf["record_count"], 5_000_000_029)
    eng = program.new_engine(program.build_config(store),
                             program.build_device(conf["device"]))
    eng.prepare_structural(s.op_types, s.keys, s.scan_lens)
    pending = eng.temporal_pass(s.arrivals)
    res = eng.finalize([reference.departures(q, a)
                        for q, a in pending.queues], pending=pending)
    jobs = program.job_arrays(pending.job_log)
    kpm = store["memtable_size"] // store["kv_size"]
    svc = reference.services(
        s.op_types, s.arrivals, res.get_reads, jobs,
        *program.stall_arrays(pending.stall_events), conf["device"],
        conf["service"], res.get_probed,
        reference.scan_delivered(s.op_types, s.keys, s.scan_lens, kpm),
        store["kv_size"])
    sc = s.op_types == reference.SCAN
    np.testing.assert_allclose(svc, pending.queues[0][0], rtol=1e-12, atol=0)
    comp = jobs["compact"]
    running = (np.searchsorted(np.sort(jobs["t_start"][comp]),
                               s.arrivals[sc], side="right")
               - np.searchsorted(np.sort(jobs["t_finish"][comp]),
                                 s.arrivals[sc], side="right"))
    assert (running > 0).any() and (res.get_probed[sc] > 0).all()
    assert (res.get_reads[sc] > 0).all()


def test_per_call_checks_are_files_named_by_the_kernels():
    for name in ("merge_path", "fence_rank"):
        kernel = program.Kernel.load(catalog.BENCH, name)
        assert callable(catalog.call_check(kernel.reference))
    merge = catalog.call_check("stable_merge")
    args = ([1, 3], [10, 11], [1, 2], [20, 21])
    assert not merge(args, ([1, 1, 2, 3], [10, 20, 21, 11]))
    assert merge(args, ([1, 1, 2, 3], [20, 10, 21, 11]))  # B first on a tie
    rank = catalog.call_check("rank_at_or_below")
    assert not rank(([5, 9], [4, 5, 9, 10]), np.array([0, 1, 2, 2]))
    assert rank(([5, 9], [4, 5, 9, 10]), np.array([0, 0, 2, 2]))


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
