"""The correctness check fails on a broken store.

Each test drives a whole run of a small cell on the CPU (the Pallas
kernels interpreted), with one fault planted under the timed path where
the program produces its answer, and sees ``correct`` come out false.
The sound runs and the control (the float32 reference in the program's
place) are here too.  The faults a one-chip cell of this store can have:

* a step that returns its state unchanged: a merge that hands back its
  older run and drops the newer one;
* half of the batch left out: a departure scan over the first half of
  each queue only;
* an answer altered where it is produced: one sequence number of a merge,
  one fence rank, one departure; in the temporal pass, two arrivals
  swapped in a queue, jobs timed without their per-SST latency, and GETs
  served without the inflation of the compactions they overlap.

No cell has an exchange between chips.
"""

from __future__ import annotations

import importlib
from contextlib import contextmanager

import numpy as np
import pytest

from smallcell import run_small, small

REPLAY = "vlsm-8m.ycsb-a.replay"
ROCKS = "rocksdb-64m.ycsb-a.replay"
MERGE = "repro.kernels.merge_path.ops:merge_two_runs_np"
RANK = "repro.kernels.overlap_scan.ops:fence_rank_np"
LINDLEY = "repro.kernels.lindley_scan.ops:lindley_batch_np"
TEMPORAL = "repro.core.fleet:FleetEngine.temporal_pass"
JOB_TIME = "repro.core.sim:Simulator._job_duration"
BUSY = "repro.core.sim:Simulator._busy_inflation"


@contextmanager
def planted(path: str, make):
    """``make(original)`` in the place of ``module:attr`` or
    ``module:Class.attr`` while the block runs."""
    mod_name, attr = path.split(":")
    owner = importlib.import_module(mod_name)
    *outer, attr = attr.split(".")
    for name in outer:
        owner = getattr(owner, name)
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def merge_returns_state(orig):
    def f(a_keys, a_seqs, b_keys, b_seqs):
        return np.asarray(a_keys, np.int64), np.asarray(a_seqs, np.int64)
    return f


def merge_alters_seq(orig):
    def f(*args):
        keys, seqs = orig(*args)
        seqs = np.array(seqs, np.int64)
        seqs[0] += 2                       # the next logical sequence number
        return keys, seqs
    return f


def rank_alters_one(orig):
    def f(fences, keys):
        out = np.array(orig(fences, keys))
        out[0] += 1
        return out
    return f


def lindley_half(orig):
    def f(services, arrivals, **kw):
        deps = orig(services, arrivals, **kw)
        out = []
        for d, s, a in zip(deps, services, arrivals):
            d = np.array(d)
            h = d.shape[0] // 2
            d[h:] = a[h:] + s[h:]          # the second half never queued
            out.append(d)
        return out
    return f


def lindley_alters_one(orig):
    def f(services, arrivals, **kw):
        deps = [np.array(d) for d in orig(services, arrivals, **kw)]
        deps[0][deps[0].shape[0] // 2] += 1e-3
        return deps
    return f


def arrivals_swapped(orig):
    def f(self, arrivals):
        pending = orig(self, arrivals)
        svc, arr = pending.queues[0]
        arr = np.array(arr)
        arr[-2], arr[-1] = arr[-1], arr[-2]
        pending.queues[0] = (svc, arr)
        return pending
    return f


def job_time_without_latency(orig):
    def f(self, job):
        d = self.device
        return job.bytes_read / d.read_bw + job.bytes_written / d.write_bw
    return f


def no_busy_inflation(orig):
    def f(self, st):
        return None
    return f


@pytest.mark.parametrize("cell", [REPLAY, ROCKS])
def test_sound_run_is_correct(cell):
    out = run_small(cell)
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out["check"])[-1] == "latency_gap_s"


@pytest.mark.parametrize("cell,path,fault", [
    (REPLAY, MERGE, merge_returns_state),
    (REPLAY, MERGE, merge_alters_seq),
    (REPLAY, RANK, rank_alters_one),
    (REPLAY, LINDLEY, lindley_half),
    (REPLAY, LINDLEY, lindley_alters_one),
    (REPLAY, TEMPORAL, arrivals_swapped),
    (REPLAY, JOB_TIME, job_time_without_latency),
    (REPLAY, BUSY, no_busy_inflation),
    (ROCKS, MERGE, merge_alters_seq),
    (ROCKS, TEMPORAL, arrivals_swapped),
])
def test_fault_is_caught(cell, path, fault):
    with planted(path, fault):
        out = run_small(cell)
    assert not out["correct"], out


def test_control_fails_where_the_program_passes():
    """The run phase slowed to 10 ops/s puts the clock at about 200 s,
    where float32 rounding shows as it does on the cells' 90 s clocks."""
    from lsmbench import cell as cell_mod
    from lsmbench import program
    wl = small(REPLAY, rate=10.0)
    program.select_device_tier()
    c = cell_mod.Cell(wl.name, wl.config, wl.traffic, wl.kernels, 11)
    try:
        c.setup()
        c.window(2.0)
        sound = dict((n, (v, lim)) for n, v, lim in c.check())
        control = dict((n, (v, lim)) for n, v, lim in c.check(control=True))
    finally:
        c.close()
    gap, limit = sound["latency_gap_s"]
    assert gap <= limit
    gap, limit = control["latency_gap_s"]
    assert gap > limit
