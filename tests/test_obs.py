"""The in-program recorder (``repro.obs``): off without a profiler, exact
spans and counters under one, and no effect on what the store computes."""

import ast
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (DeviceModel, FleetEngine, get_policy,
                        reset_uid_counters)
from repro.core import level_index, merge
from repro.kernels.lindley_scan import kernel as lk
from repro.kernels.lindley_scan.ops import lindley_batch_np
from repro.kernels.merge_path import kernel as mk
from repro.kernels.merge_path.ops import merge_two_runs_np
from repro.kernels.overlap_scan import kernel as ok
from repro.kernels.overlap_scan.ops import fence_rank_np
from repro.kernels.platform import bucket

ROOT = Path(__file__).resolve().parents[1]
SCALE = 1 << 16
DEV = DeviceModel.scaled(1 / 1024)

#: the span tree the store writes (docs/architecture.md, Tracing)
SPAN_NAMES = {
    "fleet.structural", "sim.setup", "sim.apply_window", "lsm.flush",
    "lsm.background", "lsm.chain", "lsm.merge", "merge_path.pack",
    "merge_path.call", "merge_path.unpack", "manifest.rank",
    "fence_rank.call", "fleet.plan_batch", "lindley.batch", "lindley.fill",
    "lindley.split", "lindley.call"}


@pytest.fixture
def profiling(tmp_path):
    """A JAX profiler session around the test, host Python tracing off."""
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _stream(n=4_000, seed=5):
    rng = np.random.default_rng(seed)
    ops = (rng.random(n) < 0.3).astype(np.uint8)
    keys = rng.integers(0, SCALE, n).astype(np.int64)
    return ops, keys, np.arange(n, dtype=np.float64) / 4_000.0


def _fleet_pass(ops, keys, arr):
    reset_uid_counters()
    eng = FleetEngine(get_policy("vlsm").default_config(scale=SCALE), DEV)
    eng.prepare_structural(ops, keys)
    pending = eng.temporal_pass(arr)
    deps = lindley_batch_np([q[0] for q in pending.queues],
                            [q[1] for q in pending.queues])
    return eng.finalize(deps, pending=pending)


class _Clock:
    """A stand-in for ``time``: ``perf_counter_ns`` reads ``now``."""

    def __init__(self):
        self.now = 0

    def perf_counter_ns(self) -> int:
        return self.now


def _xplane_spans(trace_dir: Path, names: set[str]) -> list[tuple]:
    """``(name, start_ns, end_ns)`` of the host events ``names`` in the
    profiler's trace under ``trace_dir``."""
    (path,) = trace_dir.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for plane in data.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name in names)


# ------------------------------------------------------------------- off
def test_off_without_a_profiler_records_nothing():
    obs.reset()
    assert not obs.enabled()
    assert obs.span("fleet.structural") is obs.NOOP
    obs.count("merge_path.calls", 3)
    _fleet_pass(*_stream(1_500))
    assert obs.counters() == {}
    assert obs.total_seconds() == {} and obs.self_seconds() == {}


# -------------------------------------------------------------------- on
def test_spans_nest_in_the_profiler_trace(tmp_path):
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert obs.enabled()
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                pass
            with obs.span("t.inner"):
                with obs.span("t.leaf"):
                    pass
        with obs.span("t.other"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = _xplane_spans(tmp_path, {"t.outer", "t.inner", "t.leaf", "t.other"})
    assert [n for n, *_ in got] == ["t.inner", "t.inner", "t.leaf",
                                    "t.other", "t.outer"]
    (outer,) = [s for s in got if s[0] == "t.outer"]
    (other,) = [s for s in got if s[0] == "t.other"]
    inner = [s for s in got if s[0] == "t.inner"]
    (leaf,) = [s for s in got if s[0] == "t.leaf"]
    assert all(outer[1] <= s[1] <= s[2] <= outer[2] for s in inner)
    assert inner[1][1] <= leaf[1] <= leaf[2] <= inner[1][2]
    assert other[1] >= outer[2]
    assert set(obs.total_seconds()) == {"t.outer", "t.inner", "t.leaf",
                                        "t.other"}


def test_self_seconds_less_the_direct_children(profiling, monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(obs, "time", clock)
    with obs.span("x.root"):                    # 0 .. 100
        clock.now = 10
        with obs.span("x.a"):                   # 10 .. 40
            clock.now = 15
            with obs.span("x.b"):               # 15 .. 25
                clock.now = 25
            clock.now = 40
        clock.now = 50
        with obs.span("x.a"):                   # 50 .. 70
            clock.now = 70
        clock.now = 100
    clock.now = 200
    with obs.span("x.c"):                       # 200 .. 260
        clock.now = 260
    assert obs.total_seconds() == pytest.approx(
        {"x.root": 100e-9, "x.a": 50e-9, "x.b": 10e-9, "x.c": 60e-9})
    assert obs.self_seconds() == pytest.approx(
        {"x.root": 50e-9, "x.a": 40e-9, "x.b": 10e-9, "x.c": 60e-9})


def test_reset_forgets_spans_and_counters(profiling):
    with obs.span("t.outer"):
        obs.count("t.n", 2)
        obs.count("t.n")
    assert obs.counters() == {"t.n": 3}
    assert set(obs.self_seconds()) == {"t.outer"}
    obs.reset()
    assert obs.counters() == {} and obs.total_seconds() == {}
    assert obs.self_seconds() == {}


def test_traced_calls_keep_their_signature_and_answer(profiling):
    @obs.traced("t.fn")
    def fn(a, b=2):
        """doc"""
        return a * b
    assert fn(3) == 6 and fn.__doc__ == "doc" and fn.__wrapped__(1) == 2
    assert set(obs.total_seconds()) == {"t.fn"}


# ------------------------------------------------------------- counters
def test_merge_counts_calls_sizes_padding_and_bytes(profiling):
    a = np.arange(0, 3_000, 2, dtype=np.int64)          # 1,500 keys
    b = np.arange(1, 601, 2, dtype=np.int64)            # 300 keys
    merge_two_runs_np(a, np.arange(a.size), b, np.arange(b.size))
    pa, pb = bucket(1_500, mk.BLOCK), bucket(300, mk.BLOCK)
    packed = mk.PLANES * (pa + mk.BLOCK + pb + mk.BLOCK) * 4
    assert obs.counters() == {
        "merge_path.calls": 1, "merge_path.keys": 1_800,
        "merge_path.padded_keys": pa + pb, "merge_path.h2d_bytes": packed,
        "merge_path.d2h_bytes": (pa + pb) * mk.PLANES * 4}
    assert set(obs.total_seconds()) == {
        "merge_path.pack", "merge_path.call", "merge_path.unpack"}


def test_fence_rank_counts_calls_sizes_padding_and_bytes(profiling):
    fences = np.arange(0, 4_000, 20, dtype=np.int64)    # 200 fences
    keys = np.arange(50, dtype=np.int64) * 77
    fence_rank_np(fences, keys)
    f_pad, k_pad = bucket(200, ok.TILE), bucket(50, ok.BLOCK)
    assert obs.counters() == {
        "fence_rank.calls": 1, "fence_rank.queries": 50,
        "fence_rank.padded_queries": k_pad,
        "fence_rank.h2d_bytes": 2 * f_pad * 4 + 2 * k_pad * 4,
        "fence_rank.d2h_bytes": k_pad * 4}
    assert set(obs.total_seconds()) == {"fence_rank.call"}


def test_lindley_counts_calls_sizes_padding_and_bytes(profiling):
    lens = (3_000, 100, 5_000, 900)
    r = np.random.default_rng(2)
    svc = [r.random(n) * 1e-3 for n in lens]
    arr = [np.sort(r.random(n)) for n in lens]
    lindley_batch_np(svc, arr, backend="pallas")
    rows: dict[int, int] = {}
    for n in lens:
        rows[bucket(n, lk.BLOCK)] = rows.get(bucket(n, lk.BLOCK), 0) + 1
    padded = sum(n_pad * b for n_pad, b in rows.items())
    assert obs.counters() == {
        "lindley.ops": sum(lens), "lindley.padded_ops": padded,
        "lindley.h2d_bytes": 4 * padded * 4 + sum(2 * b * 4
                                                  for b in rows.values()),
        "lindley.d2h_bytes": 2 * padded * 4}
    total, own = obs.total_seconds(), obs.self_seconds()
    assert set(total) == {"lindley.batch", "lindley.fill", "lindley.split",
                          "lindley.call"}
    # every other span runs inside the batch
    assert own["lindley.batch"] == pytest.approx(
        total["lindley.batch"] - total["lindley.fill"]
        - total["lindley.split"] - total["lindley.call"], abs=1e-12)


# ------------------------------------------------------------------ names
def _harness_span_names() -> set[str]:
    """``PHASES`` and ``WINDOW`` of the benchmark's trace reduction, which
    keeps host spans by these names."""
    tree = ast.parse((ROOT / "bench" / "lsmbench" / "trace.py").read_text())
    got = {t.id: ast.literal_eval(node.value) for node in tree.body
           if isinstance(node, ast.Assign) for t in node.targets
           if isinstance(t, ast.Name) and t.id in ("PHASES", "WINDOW")}
    return set(got["PHASES"]) | {got["WINDOW"]}


def test_program_span_names_are_dotted_and_not_the_harness_names():
    pattern = re.compile(r"obs\.(?:span|traced)\(\s*\"([^\"]+)\"")
    found = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        found |= set(pattern.findall(path.read_text()))
    assert found == SPAN_NAMES
    harness = _harness_span_names()
    assert harness >= {"structural", "lindley", "window"}
    assert all("." in n for n in found) and not found & harness


# ------------------------------------------------------------ determinism
def test_a_fleet_pass_is_byte_identical_with_the_recorder_on(tmp_path):
    ops, keys, arr = _stream()
    merge.set_backend("pallas")
    level_index.set_backend("pallas")
    try:
        off = _fleet_pass(ops, keys, arr)
        obs.reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            on = _fleet_pass(ops, keys, arr)
        finally:
            jax.profiler.stop_trace()
    finally:
        merge.set_backend("numpy")
        level_index.set_backend("numpy")
    assert set(obs.total_seconds()) == SPAN_NAMES
    assert obs.counters()["merge_path.calls"] > 0
    assert obs.counters()["fence_rank.calls"] > 0
    assert off.latency.tobytes() == on.latency.tobytes()
    assert off.get_reads.tobytes() == on.get_reads.tobytes()
    assert off.stall_events == on.stall_events

    def jobs(res):
        return [(j.kind, j.level, j.bytes_read, j.bytes_written, j.n_in_ssts,
                 j.n_out_ssts, j.uid, j.chain_id, j.t_start, j.t_finish)
                for j in res.job_log]
    assert jobs(off) == jobs(on)
    assert off.summary() == on.summary()
