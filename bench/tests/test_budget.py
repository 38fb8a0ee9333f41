"""The pass budget, and the kernels a cell names beyond its traffic's.

The budget is set through ``cell.pass_budget``, the one function that
computes it, and a pass is slowed by a sleep in the store's structural
replay, so that no test waits for a slow program.
"""

from __future__ import annotations

import dataclasses
import json
import shutil
import signal
import subprocess
import sys
import time

import pytest

from lsmbench import catalog, cli, program
from lsmbench import cell as cell_mod
from smallcell import small

REPLAY = "vlsm-8m.ycsb-a.replay"
SCANS = "vlsm-8m.ycsb-e.replay"
#: the cells of the benchmark that no cell file extends
CELLS = (REPLAY, "rocksdb-64m.ycsb-a.replay")
#: the kernels of those cells, in order, as their traffic file names them
KERNELS = ("merge_path", "fence_rank", "lindley_scan")
ARGS = ["--workload", REPLAY, "--seed", "4011052891", "--seconds", "2",
        "--trace", "0"]


def _slow_structural(monkeypatch, after: int) -> list[float]:
    """Every structural replay after the first ``after`` sleeps for ten
    minutes first; returns the times at which each replay began."""
    from repro.core.fleet import FleetEngine
    real = FleetEngine.prepare_structural
    began: list[float] = []

    def prepare_structural(self, *args, **kw):
        began.append(time.perf_counter())
        if len(began) > after:
            time.sleep(600)
        return real(self, *args, **kw)

    monkeypatch.setattr(FleetEngine, "prepare_structural",
                        prepare_structural)
    return began


def _disarmed(handler) -> bool:
    return (signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
            and signal.getsignal(signal.SIGALRM) is handler)


# ------------------------------------------------------------ the budget
@pytest.mark.parametrize("seconds,budget", [(51, 102), (30, 60), (2, 60),
                                            (0.5, 60)])
def test_pass_budget_is_twice_the_window_and_at_least_a_minute(seconds,
                                                               budget):
    assert cell_mod.pass_budget(seconds) == budget


def test_a_warm_up_pass_past_its_budget_is_refused(monkeypatch):
    """The run ends with no result, and the message names the pass and
    its phase, within the budget and a second of the slow replay."""
    monkeypatch.setattr(cell_mod, "pass_budget", lambda seconds: 2.0)
    began = _slow_structural(monkeypatch, after=0)
    handler = signal.getsignal(signal.SIGALRM)
    with pytest.raises(cli.Refused) as refused:
        cli.run(ARGS, time.perf_counter(), require_tpu=False,
                wl=small(REPLAY))
    took = time.perf_counter() - began[0]
    msg = str(refused.value)
    assert "warm-up pass 0 ran past its budget of 2 s" in msg
    assert "in phase structural" in msg
    assert took < 2.0 + 1.0, took
    assert len(began) == 1
    assert _disarmed(handler)


def test_a_timed_pass_past_its_budget_fails_the_run(monkeypatch, capsys):
    """Warm-up passes get a long budget, timed passes a short one; the
    first timed pass is slowed.  The window ends with that pass failed,
    and the run is not correct."""
    wl = small(REPLAY, fields={"warm_passes": 1})
    began = _slow_structural(monkeypatch, after=1)
    monkeypatch.setattr(cell_mod, "pass_budget",
                        lambda seconds: 600.0 if not began else 2.0)
    handler = signal.getsignal(signal.SIGALRM)
    out = cli.run(ARGS, time.perf_counter(), require_tpu=False, wl=wl)
    err = capsys.readouterr().err
    assert (out["correct"], out["attempted"], out["failed"]) \
        == (False, 1, 1)
    assert out["check"]["kept_passes_missing"]["value"] > 0
    assert "failed: pass 1: OverBudget: pass 1 ran past its budget" in err
    assert "in phase structural" in err
    assert len(began) == 2
    assert _disarmed(handler)


def test_a_pass_that_never_yields_is_ended_by_the_backstop():
    """A pass that waits in C with the budget's signal kept from it, so
    that the interpreter never runs the signal handler, ends the process,
    non-zero, saying why."""
    script = (
        "import signal, sys, time\n"
        f"sys.path[:0] = [{str(catalog.BENCH)!r}]\n"
        "from lsmbench import cell\n"
        "cell.BACKSTOP_S = 0.5\n"
        "cell.pass_budget = lambda seconds: 0.5\n"
        "with cell.budget('warm-up pass 0', 51, lambda: 'structural'):\n"
        "    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})\n"
        "    time.sleep(600)\n"
        "print('not ended')\n")
    t0 = time.perf_counter()
    done = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=60)
    assert time.perf_counter() - t0 < 30
    assert done.returncode == 3, done.stderr
    assert "not ended" not in done.stdout
    for words in ("warm-up pass 0 ran past its budget of 0.5 s",
                  "in phase structural", "never regained control"):
        assert words in done.stderr, done.stderr


def test_the_budget_is_disarmed_after_a_pass_in_time():
    handler = signal.getsignal(signal.SIGALRM)
    with cell_mod.budget("pass 1", 51, lambda: "finalize"):
        assert signal.getitimer(signal.ITIMER_REAL)[0] > 100
    assert _disarmed(handler)
    with pytest.raises(ValueError):
        with cell_mod.budget("pass 2", 51, lambda: "finalize"):
            raise ValueError("a failing pass")
    assert _disarmed(handler)


# ------------------------------------------------- kernels a cell names
def _scratch_root(tmp_path, extra: list[str]):
    """A copy of the benchmark's data with a YCSB-E cell on ``vlsm-8m``
    whose cell file names ``extra``, and a kernel file ``scan_merge``
    whose entries the program does not have."""
    bm = catalog.benchmark()
    bm["workloads"].append({
        "name": SCANS, "config": "vlsm-8m", "traffic": "ycsb-e.replay",
        "chips": 1, "why": "range scans"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    for part in ("configs", "traffic", "kernels"):
        shutil.copytree(catalog.BENCH / part, tmp_path / "bench" / part)
    (tmp_path / "bench" / "kernels" / "scan_merge.json").write_text(
        json.dumps({"entry": "repro.kernels.no_such.kernel:scan_merge_call",
                    "host_entry": "repro.kernels.no_such.ops:scan_merge_np",
                    "reference": "", "programs": ["jit_scan_merge_call"]}))
    (tmp_path / "bench" / "cells").mkdir()
    (tmp_path / "bench" / "cells" / f"{SCANS}.json").write_text(
        json.dumps({"kernels": extra}))
    return tmp_path


def test_a_cell_file_adds_kernels_after_the_traffics_once(tmp_path):
    root = _scratch_root(tmp_path, ["scan_merge", "fence_rank",
                                    "scan_merge"])
    wl = catalog.workload(SCANS, root)
    assert tuple(wl.kernels) == (*KERNELS, "scan_merge")
    assert wl.kernels["scan_merge"].programs == ("jit_scan_merge_call",)
    # the other cells of that root are as before
    assert tuple(catalog.workload(REPLAY, root).kernels) == KERNELS


def test_a_kernel_the_program_lacks_is_tolerated(tmp_path, capsys):
    """The extra kernel has no entry in the program: no tap, no per-call
    check, no ladder, ``entry gone`` in place of the compiled-kernel
    refusal, and nothing in the trace; the run is as without it."""
    root = _scratch_root(tmp_path, ["scan_merge"])
    gone = catalog.workload(SCANS, root).kernels["scan_merge"]
    assert gone.compiled_shapes() is None
    assert not program.Tap(gone, 1.0, None).install()
    wl = small(REPLAY)
    wl = dataclasses.replace(wl, kernels={**wl.kernels, "scan_merge": gone})
    out = cli.run(ARGS[:-1] + ["1"], time.perf_counter(), require_tpu=False,
                  wl=wl)
    err = capsys.readouterr().err
    assert out["correct"], err
    assert "kernel scan_merge: entry gone" in err
    assert "scan_merge_calls_differ" not in out["check"]
    assert "structural_s_per_pass" in out["metrics"]
    assert "busy_s" in out["device"]


def test_the_cells_kernels_are_their_traffics():
    """The two cells that stand have no cell file and keep their
    traffic's kernels; a cell added later may name more after those."""
    for name in CELLS:
        assert not (catalog.BENCH / "cells" / f"{name}.json").exists()
        assert tuple(catalog.workload(name).kernels) == KERNELS
        assert tuple(small(name).kernels) == KERNELS
    scans = tuple(small(SCANS).kernels)
    assert scans[:len(KERNELS)] == catalog.traffic("ycsb-e.replay").kernels
