"""One cell: its set-up, its passes, the measured window, and the check.

A *pass* produces one ``SimResult`` through the store's phases on the
device tier, from input that no earlier pass of the process has seen: a
fresh engine replays the seed's stream with its keys mapped through the
pass's own bijection of the key space (see ``streams.Stream.mapped``),
then the temporal pass, Lindley and finalize.

The window rule: passes start while the elapsed time is under the
window's length; the window ends when the last pass that started ends.
The harness keeps a few passes for the check, drawn from the seed, and
with them a seeded sample of each tapped kernel's calls in those passes.

The pass budget: no pass, warm-up or timed, may run longer than
``pass_budget`` of the window.  A program slower than that cannot serve
the cell at its size, and the run ends (see ``budget``).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import signal
import sys
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from . import catalog, program, reference
from .streams import Stream, Traffic, key_map, rng
from .trace import WINDOW


# Seconds past its budget after which a pass that never hands control back
# to the interpreter (so that the budget's signal handler cannot run) ends
# the process.
BACKSTOP_S = 30.0


def pass_budget(seconds: float) -> float:
    """The longest a pass may take in a run whose window is ``seconds``
    long: twice the window, and never under 60 s.  A pass longer than
    twice the window leaves a window of at most one pass, which gives no
    steady ``ops_per_s``; the floor leaves short windows (the harness's
    CPU tests) room for passes of interpreted kernels."""
    return max(2.0 * seconds, 60.0)


class OverBudget(BaseException):
    """A pass ran past its budget.  Not an ``Exception``, so that no
    handler inside the program under test can swallow it."""


@contextlib.contextmanager
def budget(label: str, seconds: float, phase):
    """Interrupts the body once it has run ``pass_budget(seconds)``: an
    interval timer raises ``OverBudget`` in the main thread, naming
    ``label``, the phase ``phase()`` returns and the seconds elapsed.
    Where the interpreter never regains control, a backstop thread prints
    the same message and ends the process ``BACKSTOP_S`` later.  Both are
    disarmed when the body ends, so neither can fire outside it."""
    limit = pass_budget(seconds)
    t0 = time.perf_counter()

    def message() -> str:
        return (f"{label} ran past its budget of {limit:g} s (twice the "
                f"{seconds:g} s window, at least 60 s) in phase {phase()}, "
                f"{time.perf_counter() - t0:.1f} s in: the program cannot "
                f"serve this cell at its size")

    def backstop() -> None:
        print(f"bench: {message()}; the interpreter never regained "
              f"control", file=sys.stderr, flush=True)
        os._exit(3)

    stop = threading.Timer(limit + BACKSTOP_S, backstop)
    stop.daemon = True

    def on_alarm(_signum, _frame):
        stop.cancel()      # the interpreter has control: no backstop
        raise OverBudget(message())

    old = signal.signal(signal.SIGALRM, on_alarm)
    stop.start()
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        stop.cancel()
        signal.signal(signal.SIGALRM, old)


@dataclass
class PassRecord:
    index: int
    ops: int
    start: float
    wall_s: float
    phases: dict[str, float]
    compaction_keys: int      # keys read + written by the pass's compactions


@dataclass
class Kept:
    """A pass kept for the check: its store, what its temporal pass and
    Lindley produced, and the map that made its stream from the seed's.
    ``get_probed`` (the files each read op opened) is kept for mixes with
    SCANs only, whose service depends on it."""

    index: int
    key_map: tuple[int, int]
    engine: object
    queues: list
    latency: np.ndarray
    get_reads: np.ndarray
    get_probed: np.ndarray | None
    jobs: dict
    stalls: tuple[np.ndarray, np.ndarray]
    shard_ids: np.ndarray | None
    calls: dict[str, list] = field(default_factory=dict)


class Reservoir:
    """The passes kept for the check: ``k`` of the window's first
    ``among`` passes, drawn from the seed.  A kept pass's queues and
    latencies are copied into buffers made in set-up, so that keeping
    them leaves the store's own allocations as they would be."""

    def __init__(self, k: int, among: int, seed: int):
        self.chosen = sorted(rng(seed, 404).choice(
            np.arange(1, among + 1), size=min(k, among),
            replace=False).tolist())
        self.kept: list[Kept | None] = [None] * len(self.chosen)

    def slot(self, i: int) -> int | None:
        """The slot pass ``i`` fills, or None."""
        return self.chosen.index(i) if i in self.chosen else None


class Cell:
    def __init__(self, name: str, conf: dict, traffic: Traffic,
                 kernels: dict[str, program.Kernel], seed: int,
                 annotate: bool = False):
        self.name = name
        self.conf = conf
        self.traffic = traffic
        self.kernels = kernels
        self.seed = seed
        self.annotate = annotate
        self.cfg = program.build_config(conf["store"])
        self.device = program.build_device(conf["device"])
        self.record_count = int(conf["record_count"])
        self.reservoir = Reservoir(traffic.keep_passes, traffic.keep_among,
                                   seed)
        self.taps: dict[str, program.Tap] = {}
        for k in kernels.values():
            if k.reference or k.warm:
                tap = program.Tap(k, traffic.call_sample,
                                  rng(seed, 505, len(self.taps)))
                if tap.install():
                    self.taps[k.name] = tap
        self.base: Stream | None = None
        self.slots: list | None = None
        self.warm: list[PassRecord] = []
        self.passes: list[PassRecord] = []
        self.failed = 0
        self.error = ""
        self.phase = ""

    # ----------------------------------------------------------- phases
    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _arm(self, on: bool) -> None:
        for tap in self.taps.values():
            tap.armed = on
            if on:
                tap.calls = []

    def _calls(self) -> dict[str, list]:
        return {n: t.calls for n, t in self.taps.items()}

    def setup(self, seconds: float) -> None:
        """The seed's stream, the warm-up passes (each over a map of its
        own, each within the budget of a ``seconds`` window: past it,
        ``OverBudget``), then the size ladder, so that every program the
        window uses is compiled or loaded before it starts."""
        self.base = self.traffic.base_stream(self.record_count, self.seed)
        for tap in self.taps.values():
            tap.sizes = set() if tap.kernel.warm else None
        self.warm = []
        for w in range(self.traffic.warm_passes):
            with budget(f"warm-up pass {-w}", seconds, lambda: self.phase):
                self.warm.append(self.run_pass(-w, keep=False))
        t = time.perf_counter()
        self.ladder_calls = self._warm_ladder()
        self.ladder_s = time.perf_counter() - t

    def _warm_ladder(self) -> int:
        """Calls each kernel whose sizes vary at every combination of
        powers of two from half the least to twice the most that the
        warm-up passes used on each axis.  A pass's merges and fence ranks
        take sizes that depend on its key map (a later pass may reach a
        size class no warm-up pass did); the ladder compiles their
        neighbours too.  Returns the number of calls."""
        n = 0
        pool = np.sort(rng(self.seed, 707).integers(
            0, 1 << 48, 1 << 22, dtype=np.int64))
        for tap in self.taps.values():
            seen, tap.sizes = tap.sizes, None
            if not seen:
                continue
            axes = []
            for sizes in zip(*seen):
                lo = _pow2(min(sizes)) // 2
                hi = min(_pow2(max(sizes)) * 2, pool.shape[0])
                axes.append([1 << e for e in range(max(lo, 1).bit_length() - 1,
                                                   hi.bit_length())])
            for combo in itertools.product(*axes):
                tap.fn(*tap.kernel.make_args(combo, pool))
                n += 1
        return n

    def run_pass(self, i: int, keep: bool = True) -> PassRecord:
        self.phase = "structural"
        slot = self.reservoir.slot(i) if keep else None
        t0 = time.perf_counter()
        phases = {}
        kmap = key_map(self.seed, i)
        stream = self.base.mapped(kmap)
        self._arm(slot is not None)
        try:
            with self._span("structural"):
                eng = program.new_engine(self.cfg, self.device)
                eng.prepare_structural(stream.op_types, stream.keys,
                                       stream.scan_lens)
        finally:
            self._arm(False)
        phases["structural"] = time.perf_counter() - t0
        t = time.perf_counter()
        self.phase = "temporal"
        with self._span("temporal"):
            pending = eng.temporal_pass(stream.arrivals)
        phases["temporal"] = time.perf_counter() - t
        t = time.perf_counter()
        self.phase = "lindley"
        with self._span("lindley"):
            deps = program.lindley([q[0] for q in pending.queues],
                                   [q[1] for q in pending.queues])
        phases["lindley"] = time.perf_counter() - t
        t = time.perf_counter()
        self.phase = "finalize"
        with self._span("finalize"):
            res = eng.finalize(deps, pending=pending)
        t1 = time.perf_counter()
        phases["finalize"] = t1 - t
        read, written = program.compaction_bytes(pending.job_log,
                                                 self.cfg.kv_size)
        rec = PassRecord(i, stream.n, t0, t1 - t0, phases, read + written)
        if self.slots is None:
            self._make_slots(pending.queues, stream.n)
        if slot is not None:
            queues, latency, get_reads, get_probed = self.slots[slot]
            for (s, a), (qs, qa) in zip(queues, pending.queues):
                np.copyto(s, qs)
                np.copyto(a, qa)
            np.copyto(latency, res.latency)
            np.copyto(get_reads, res.get_reads)
            if get_probed is not None:
                np.copyto(get_probed, res.get_probed)
            self.reservoir.kept[slot] = Kept(
                i, kmap, eng, queues, latency, get_reads, get_probed,
                program.job_arrays(pending.job_log),
                program.stall_arrays(pending.stall_events),
                res.shard_ids, self._calls())
        return rec

    def _make_slots(self, queues, n: int) -> None:
        """One set of buffers per kept pass, written through once here so
        that no page of them is first touched inside the window."""
        def buf(size, dtype=np.float64):
            b = np.empty(size, dtype)
            b.fill(0)
            return b
        self.slots = [([(buf(q[0].shape[0]), buf(q[0].shape[0]))
                        for q in queues], buf(n), buf(n, np.int32),
                       buf(n, np.int32) if self.traffic.scans else None)
                      for _ in self.reservoir.chosen]

    def window(self, seconds: float) -> tuple[float, float]:
        """Passes 1, 2, ... while under ``seconds``, each within its
        budget; returns the window's start and end on the host clock."""
        t0 = time.perf_counter()
        i = 1
        with self._span(WINDOW):
            while time.perf_counter() - t0 < seconds:
                try:
                    with budget(f"pass {i}", seconds, lambda: self.phase):
                        self.passes.append(self.run_pass(i))
                # a pass that fails or runs past its budget ends the window
                except (Exception, OverBudget) as e:
                    self.failed += 1
                    self.error = f"pass {i}: {type(e).__name__}: {e}"
                    break
                i += 1
        return t0, time.perf_counter()

    def close(self) -> None:
        """Take the taps out of the program."""
        for tap in self.taps.values():
            tap.remove()
        self.taps = {}

    # ------------------------------------------------------------- check
    def check(self, control: bool = False) -> list[tuple[str, float, float]]:
        """Every number compared, with its limit: ``(name, value, limit)``.

        For each kept pass: its store's merged view and a GET batch
        against the latest writes of its stream; each tapped call against
        its reference; its queues' arrivals against the stream's; its jobs'
        times and its stalls against the device model; and every op's
        latency against Lindley's recursion over queues the reference
        builds itself (the stream's arrivals, and service by the device
        model).  With ``control``, the latencies compared are the float32
        reference's instead of the program's.

        A mix with SCANs adds a SCAN batch on each kept store against the
        reference's answers, and each SCAN's delivered count: the one its
        service in the program's queue implies (the service less the
        reference's service of that SCAN, in keys at the read bandwidth)
        against the reference's."""
        kept = [k for k in self.reservoir.kept if k is not None]
        device, store = self.conf["device"], self.conf["store"]
        kpm = max(1, store["memtable_size"] // store["kv_size"])
        key_s = store["kv_size"] / device["read_bw"]
        scans = self.traffic.scans
        view = gets = arrivals = sched = misplaced = 0
        scans_differ = delivered_differ = 0
        job_gap = gap = 0.0
        differ = {n: 0 for n in self.taps}
        for k in kept:
            stream = self.base.mapped(k.key_map)
            tree = k.engine.trees[0]
            written = reference.latest_writes(stream.op_types, stream.keys)
            view += _view_differs(tree.merged_view(), written)
            probe = _probe_keys(stream, self.traffic.probe_keys,
                                rng(self.seed, 606, k.index + 1))
            got = np.asarray(tree.get_batch(probe)[0])
            gets += int(np.count_nonzero(
                got != reference.get_answers(written, probe)))
            delivered = None
            if scans:
                r = rng(self.seed, 808, k.index + 1)
                starts = _probe_keys(stream, self.traffic.probe_scans, r)
                lens = r.integers(1, self.traffic.max_scan_length + 1,
                                  starts.shape[0])
                scans_differ += _scans_differ(
                    program.scan(tree, starts, lens),
                    reference.scan_answers(written, starts, lens))
                delivered = reference.scan_delivered(
                    stream.op_types, stream.keys, stream.scan_lens, kpm)
            for name, calls in k.calls.items():
                check = catalog.call_check(self.kernels[name].reference)
                differ[name] += sum(check(a, o) for a, o in calls)
            jobs = k.jobs
            if jobs["t_start"].size:
                job_gap = max(job_gap, float(np.max(np.abs(
                    (jobs["t_finish"] - jobs["t_start"])
                    - reference.job_seconds(jobs, device)))))
            sched += reference.schedule_violations(jobs, device)
            stall_ops, stalls = k.stalls
            misplaced += int(np.count_nonzero(
                ~np.isin(stall_ops, reference.fill_ops(stream.op_types, kpm))
                | ~(stalls > 0)))
            svc = reference.services(stream.op_types, stream.arrivals,
                                     k.get_reads, jobs, stall_ops, stalls,
                                     device, self.conf["service"],
                                     k.get_probed, delivered,
                                     store["kv_size"])
            for s, (q_svc, arr) in enumerate(k.queues):
                mine = slice(None) if k.shard_ids is None \
                    else k.shard_ids == s
                want_a = stream.arrivals[mine]
                arrivals += int(np.count_nonzero(arr != want_a)) \
                    if arr.shape == want_a.shape else int(want_a.shape[0])
                ref = reference.departures(svc[mine], want_a) - want_a
                if control:
                    lat = reference.departures(svc[mine], want_a,
                                               np.float32) - want_a
                else:
                    lat = k.latency[mine]
                gap = max(gap, float(np.max(np.abs(lat - ref))))
                if scans:
                    sc = stream.op_types[mine] == reference.SCAN
                    if q_svc.shape == want_a.shape:
                        extra = (q_svc[sc] - svc[mine][sc]) / key_s
                        delivered_differ += int(np.count_nonzero(
                            np.rint(extra) != 0))
                    else:
                        delivered_differ += int(np.count_nonzero(sc))
        missing = len(self.reservoir.chosen) - len(kept)
        limits = self.traffic.limits
        out = [("kept_passes_missing", missing, 0),
               ("view_keys_differ", view, 0), ("gets_differ", gets, 0)]
        if scans:
            out += [("scans_differ", scans_differ, 0),
                    ("scan_delivered_differ", delivered_differ, 0)]
        out += [(f"{n}_calls_differ", v, 0) for n, v in differ.items()]
        out += [("arrivals_differ", arrivals, 0),
                ("schedule_violations", sched, 0),
                ("stalls_misplaced", misplaced, 0),
                ("job_time_gap_s", job_gap, float(limits["job_time_gap_s"])),
                ("latency_gap_s", gap, float(limits["latency_gap_s"]))]
        return out

    def checked_counts(self) -> dict[str, int]:
        kept = [k for k in self.reservoir.kept if k is not None]
        out = {"passes": len(kept),
               "jobs": sum(int(k.jobs["t_start"].size) for k in kept),
               "stalls": sum(int(k.stalls[0].size) for k in kept)}
        if self.traffic.scans:
            out["scans"] = len(kept) * int(np.count_nonzero(
                self.base.op_types == reference.SCAN))
            out["probe_scans"] = len(kept) * self.traffic.probe_scans
        for n, tap in self.taps.items():
            out[f"{n}_calls"] = sum(len(k.calls.get(n, ())) for k in kept)
            out[f"{n}_calls_seen"] = tap.seen
        return out


def _pow2(n: int) -> int:
    """The least power of two at or above ``n`` (1 for 0)."""
    return 1 << max(0, int(n) - 1).bit_length()


def _view_differs(view: dict, written: tuple[np.ndarray, np.ndarray]) -> int:
    """Keys whose presence or sequence number differs between the store's
    merged view and the reference."""
    uk, useq = written
    got_k = np.fromiter(view.keys(), np.int64, len(view))
    got_s = np.fromiter(view.values(), np.int64, len(view))
    both, i_ref, i_got = np.intersect1d(uk, got_k, assume_unique=True,
                                        return_indices=True)
    only = (uk.shape[0] - both.shape[0]) + (got_k.shape[0] - both.shape[0])
    return int(only + np.count_nonzero(useq[i_ref] != got_s[i_got]))


def _probe_keys(stream: Stream, n: int, r: np.random.Generator
                ) -> np.ndarray:
    """Half keys of the stream, half keys drawn afresh (almost all
    absent), from ``r``."""
    present = stream.keys[r.integers(0, stream.n, n // 2)]
    lo, hi = int(stream.keys.min()), int(stream.keys.max())
    fresh = r.integers(lo, hi + 1, n - n // 2, dtype=np.int64)
    return np.concatenate([present, fresh])


def _scans_differ(got, want) -> int:
    """SCANs whose keys or sequence numbers differ: ``got`` and ``want``
    are flattened ``(keys, seqs, offsets)``."""
    g_k, g_s, g_off = got
    w_k, w_s, w_off = want
    n = w_off.shape[0] - 1
    if g_off.shape != w_off.shape:
        return n
    bad = np.diff(g_off) != np.diff(w_off)
    same = np.nonzero(~bad)[0]
    cnt = np.diff(w_off)[same]
    within = np.arange(int(cnt.sum())) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    gi = np.repeat(g_off[same], cnt) + within
    wi = np.repeat(w_off[same], cnt) + within
    wrong = (g_k[gi] != w_k[wi]) | (g_s[gi] != w_s[wi])
    bad[np.repeat(same, cnt)[wrong]] = True
    return int(np.count_nonzero(bad))
