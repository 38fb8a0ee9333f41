"""The readers of the program's own spans and counters (``repro.obs``)."""

from __future__ import annotations

import sys

import jax
import pytest
from smallcell import run_small

from lsmbench import catalog
from lsmbench.cell import PassRecord
from lsmbench.cli import Readings

from repro import obs

METRICS = ("apply_window_s_per_pass", "compaction_host_s_per_pass",
           "merge_host_s_per_pass", "merge_call_s_per_pass",
           "manifest_rank_s_per_pass", "lindley_host_s_per_pass",
           "merge_calls_per_pass", "merge_pad_share", "h2d_bytes_per_pass",
           "sim_setup_s_per_pass", "fence_rank_call_s_per_pass",
           "fence_rank_calls_per_pass", "fence_rank_pad_share",
           "lindley_split_s_per_pass", "lindley_pad_share",
           "d2h_bytes_per_pass")
MS = 1_000_000


class _Clock:
    """A stand-in for ``time``: ``perf_counter_ns`` reads ``now``."""

    def __init__(self):
        self.now = 0

    def perf_counter_ns(self) -> int:
        return self.now


def _readings(n_passes: int) -> Readings:
    passes = [PassRecord(i, 100, 0.0, 1.0, {}, 0)
              for i in range(1, n_passes + 1)]
    return Readings(passes, window_s=2.0, setup_s=1.0, compiles_in_window=0,
                    peaks={}, kv_size=200)


def _read_all(r) -> dict:
    return {m: catalog.reader(m)(r) for m in METRICS}


def test_every_reader_is_a_per_layer_metric_of_both_cells():
    """Both YCSB-A replay cells report every reader; a cell added later
    need not."""
    entries = {m["name"]: m for m in catalog.benchmark()["per_layer"]}
    cells = {"vlsm-8m.ycsb-a.replay", "rocksdb-64m.ycsb-a.replay"}
    for m in METRICS:
        assert entries[m]["moves"] == "ops_per_s"
        assert cells <= set(entries[m]["workloads"])


def _spans(clock: _Clock, tree: tuple) -> None:
    """Open the spans of ``tree`` (``(name, t0_ms, t1_ms, children)``) in
    the recorder, with ``clock`` at each start and end."""
    name, t0, t1, children = tree
    clock.now = t0 * MS
    with obs.span(name):
        for child in children:
            _spans(clock, child)
        clock.now = t1 * MS


def test_readers_on_a_recorder_filled_under_the_profiler(tmp_path,
                                                         monkeypatch):
    structural = ("fleet.structural", 0, 1000, (
        ("sim.setup", 0, 40, ()),
        ("sim.apply_window", 40, 300, (
            ("manifest.rank", 100, 150, (
                ("fence_rank.call", 110, 140, ()),)),)),
        ("lsm.flush", 300, 900, (
            ("lsm.chain", 310, 890, (
                ("lsm.merge", 400, 800, (
                    ("merge_path.pack", 410, 450, ()),
                    ("merge_path.call", 450, 700, ()),
                    ("merge_path.unpack", 700, 720, ()))),
                ("manifest.rank", 850, 860, ()))),)),
        ("lsm.background", 900, 950, ())))
    lindley = ("lindley.batch", 1000, 1100, (
        ("lindley.fill", 1005, 1010, ()),
        ("lindley.split", 1010, 1020, ()),
        ("lindley.call", 1020, 1090, ())))
    counts = {"merge_path.calls": 6, "merge_path.keys": 1_500,
              "merge_path.padded_keys": 2_000, "merge_path.h2d_bytes": 800,
              "merge_path.d2h_bytes": 700, "fence_rank.calls": 8,
              "fence_rank.queries": 300, "fence_rank.padded_queries": 400,
              "fence_rank.h2d_bytes": 100, "fence_rank.d2h_bytes": 40,
              "lindley.ops": 900, "lindley.padded_ops": 1_000,
              "lindley.h2d_bytes": 60, "lindley.d2h_bytes": 20}
    clock = _Clock()
    monkeypatch.setattr(obs, "time", clock)
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _spans(clock, structural)
        _spans(clock, lindley)
        for name, n in counts.items():
            obs.count(name, n)
    finally:
        jax.profiler.stop_trace()
    got = _read_all(_readings(2))
    assert got == pytest.approx({
        "apply_window_s_per_pass": 0.210 / 2,
        # flush 600 - chain 580; chain 580 - merge 400 - rank 10; bg 50
        "compaction_host_s_per_pass": (0.020 + 0.170 + 0.050) / 2,
        # merge 400 - 310 of kernel-side spans, plus pack 40 and unpack 20
        "merge_host_s_per_pass": (0.090 + 0.040 + 0.020) / 2,
        "merge_call_s_per_pass": 0.250 / 2,
        "manifest_rank_s_per_pass": 0.060 / 2,
        "lindley_host_s_per_pass": 0.030 / 2,
        "merge_calls_per_pass": 3.0,
        "merge_pad_share": 25.0,
        "h2d_bytes_per_pass": 480.0,
        "sim_setup_s_per_pass": 0.040 / 2,
        "fence_rank_call_s_per_pass": 0.030 / 2,
        "fence_rank_calls_per_pass": 4.0,
        "fence_rank_pad_share": 25.0,
        "lindley_split_s_per_pass": 0.010 / 2,
        "lindley_pad_share": 10.0,
        "d2h_bytes_per_pass": 380.0})


def test_readers_find_nothing_in_an_empty_recorder():
    obs.reset()
    assert all(v is None for v in _read_all(_readings(3)).values())


def test_readers_find_nothing_in_a_program_without_a_recorder(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # import fails
    assert all(v is None for v in _read_all(_readings(3)).values())


def test_a_small_traced_cell_reports_every_recorder_metric():
    obs.reset()
    out = run_small("vlsm-8m.ycsb-a.replay", trace=1)
    assert out["correct"], out
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert all(m.get(k) is not None and m[k] > 0 for k in METRICS), m
    assert 0 < m["merge_pad_share"] < 100
    # the program's structural span sits inside the harness's phase
    passes = out["attempted"]
    fleet = obs.total_seconds()["fleet.structural"] / passes
    assert 0.9 * m["structural_s_per_pass"] <= fleet \
        <= m["structural_s_per_pass"]
    own = obs.self_seconds()["fleet.structural"] / passes
    assert own <= 0.1 * fleet
