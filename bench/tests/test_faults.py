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
  served without the inflation of the compactions they overlap;
* on a mix with SCANs (``ycsb-e.replay``): a SCAN one key short, SCANs
  that skip the memtable, SCAN service without its seek wave, and the
  SCAN lengths lost on the way into the store.

No cell has an exchange between chips.
"""

from __future__ import annotations

import ast
import importlib
from contextlib import contextmanager

import numpy as np
import pytest

from smallcell import run_small, small

REPLAY = "vlsm-8m.ycsb-a.replay"
ROCKS = "rocksdb-64m.ycsb-a.replay"
SCANS = "vlsm-8m.ycsb-e.replay"
#: the SCAN cell cut for the interpreter: each SCAN merges its runs
#: through the interpreted merge kernel (about 10 ms a SCAN), so one kept
#: pass, one warm-up pass and a SCAN batch of 256
SCANS_SMALL = {"keep_passes": 1, "keep_among": 1, "warm_passes": 1,
               "probe_scans": 256}
#: every number the check compares, in order, for a mix without SCANs
CHECKS = ["kept_passes_missing", "view_keys_differ", "gets_differ",
          "merge_path_calls_differ", "fence_rank_calls_differ",
          "arrivals_differ", "schedule_violations", "stalls_misplaced",
          "job_time_gap_s", "latency_gap_s"]
MERGE = "repro.kernels.merge_path.ops:merge_two_runs_np"
RANK = "repro.kernels.overlap_scan.ops:fence_rank_np"
LINDLEY = "repro.kernels.lindley_scan.ops:lindley_batch_np"
TEMPORAL = "repro.core.fleet:FleetEngine.temporal_pass"
JOB_TIME = "repro.core.sim:Simulator._job_duration"
BUSY = "repro.core.sim:Simulator._busy_inflation"
SCAN_IMPL = "repro.core.lsm:LSMTree._scan_impl"
MEMTABLE_SCAN = "repro.core.memtable:Memtable.scan_from"
APPLY_WINDOW = "repro.core.sim:Simulator._apply_window"
SETUP = "repro.core.sim:Simulator._setup"


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


def scan_one_short(orig):
    def f(self, start_keys, lengths):
        counts, blocks, files, keys, seqs = orig(self, start_keys, lengths)
        keep = np.ones(keys.shape[0], bool)
        keep[np.cumsum(counts)[counts > 0] - 1] = False
        return np.maximum(counts - 1, 0), blocks, files, keys[keep], seqs[keep]
    return f


def scan_skips_memtable(orig):
    def f(self, key, m):
        return np.empty(0, np.int64), np.empty(0, np.int64), False
    return f


def scan_without_seek_wave(orig):
    def f(self, shard, idx, op_types, keys, scan_lens, regions, get_reads,
          get_probed, service, block_t):
        orig(self, shard, idx, op_types, keys, scan_lens, regions,
             get_reads, get_probed, service, block_t)
        service[idx[op_types[idx] == 3]] -= self.device.io_latency
    return f


def scan_lens_dropped(orig):
    """The lengths lost on the way in: every SCAN asks for the least the
    store takes, one key."""
    def f(self, op_types, keys, arrivals, scan_lens):
        if scan_lens is not None:
            scan_lens = np.minimum(scan_lens, 1)
        return orig(self, op_types, keys, arrivals, scan_lens)
    return f


@pytest.mark.parametrize("cell", [REPLAY, ROCKS])
def test_sound_run_is_correct(cell):
    out = run_small(cell)
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out["check"]) == CHECKS


def test_sound_scan_run_is_correct(capsys):
    out = run_small(SCANS, seconds=1.0, fields=SCANS_SMALL)
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out["check"]) == (CHECKS[:3] + ["scans_differ",
                                                "scan_delivered_differ"]
                                  + CHECKS[3:])
    line = [x for x in capsys.readouterr().err.splitlines()
            if x.startswith("checked: ")][-1]
    counted = ast.literal_eval(line[len("checked: "):])
    assert counted["scans"] > 1_000 and counted["probe_scans"] == 256


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


@pytest.mark.parametrize("path,fault,number", [
    (SCAN_IMPL, scan_one_short, "scans_differ"),
    (MEMTABLE_SCAN, scan_skips_memtable, "scans_differ"),
    (APPLY_WINDOW, scan_without_seek_wave, "scan_delivered_differ"),
    (SETUP, scan_lens_dropped, "scan_delivered_differ"),
])
def test_scan_fault_is_caught(path, fault, number):
    with planted(path, fault):
        out = run_small(SCANS, seconds=1.0, fields=SCANS_SMALL)
    assert not out["correct"], out
    assert out["check"][number]["value"] > out["check"][number]["limit"]


def test_control_fails_where_the_program_passes():
    """The run phase slowed to 10 ops/s puts the clock at about 200 s,
    where float32 rounding shows as it does on the cells' 90 s clocks."""
    from lsmbench import cell as cell_mod
    from lsmbench import program
    wl = small(REPLAY, rate=10.0)
    program.select_device_tier()
    c = cell_mod.Cell(wl.name, wl.config, wl.traffic, wl.kernels, 11)
    try:
        c.setup(2.0)
        c.window(2.0)
        sound = dict((n, (v, lim)) for n, v, lim in c.check())
        control = dict((n, (v, lim)) for n, v, lim in c.check(control=True))
    finally:
        c.close()
    gap, limit = sound["latency_gap_s"]
    assert gap <= limit
    gap, limit = control["latency_gap_s"]
    assert gap > limit
