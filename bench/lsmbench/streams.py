"""Traffic: op streams and arrival schedules made from a seed.

The generators are copies of the store's own YCSB generators
(``repro.bench_kv.workloads`` and ``db_bench._load_settle_run``), kept here
so that a change to the program cannot change the benchmark's traffic:

* the load inserts ``record_count`` uniform keys in hashed (random) order,
  as YCSB's default ``insertorder=hashed`` does;
* the run phase is a YCSB core mix of GET, update, INSERT and SCAN: the
  keys of GETs, updates and SCAN starts follow YCSB's scattered Zipfian
  over the loaded population, INSERTs write fresh uniform keys (YCSB's
  ``insertorder=hashed``), and SCAN lengths are uniform in ``[1,
  max_scan_length]`` (a copy of ``workloads.make_run_a`` and
  ``make_run_e``, general only in the proportions and the longest scan);
* arrivals: the load floods at ``load_rate_ops_s``, a ``settle_s`` pause
  follows (YCSB's wait between load and run), then the run phase arrives
  at a fixed rate.

``bench/tests/test_bench.py`` pins the copies to the originals at a small
size.

A traffic file (``bench/traffic/<name>.json``) holds only parameters; one
:class:`Traffic` reads every such file.  Every timed pass replays a fresh
stream through a fresh store: the seed's stream with its keys mapped
through a bijection of the key space drawn for that pass.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

KEYSPACE = 1 << 48
PUT, GET, SCAN = 0, 1, 3          # the store's op kinds


def rng(*words: int) -> np.random.Generator:
    """A generator keyed by whole numbers of any size (seeds above 2**32
    included); the same words give the same stream."""
    return np.random.default_rng([w % (1 << 64) for w in words])


# ------------------------------------------------- copies of the generators
def load_keys(n: int, seed: int) -> np.ndarray:
    """Uniform keys in ``[0, KEYSPACE)``, in insertion order."""
    return np.random.default_rng(seed).integers(0, KEYSPACE, size=n,
                                                dtype=np.int64)


def zipf_rank_sample(m: int, n: int, theta: float, seed: int) -> np.ndarray:
    """``n`` ranks in ``[0, m)`` with probability proportional to
    ``1 / (rank + 1) ** theta``, by inverse CDF."""
    w = 1.0 / np.arange(1, m + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    u = np.random.default_rng(seed).random(n)
    return np.searchsorted(cdf, u, side="left")


def zipf_keys(population: np.ndarray, n: int, theta: float,
              seed: int) -> np.ndarray:
    """YCSB's scattered Zipfian: hot ranks spread over the key order by a
    seeded permutation of the population."""
    m = population.shape[0]
    idx = zipf_rank_sample(m, n, theta, seed)
    perm = np.random.default_rng(seed + 1).permutation(m)
    return population[perm[idx]]


def ycsb_mix(population: np.ndarray, n: int, read: float, insert: float,
             scan: float, max_scan_length: int, theta: float, seed: int
             ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """YCSB core workload ops: ``(op_types, keys, scan_lens)``.  One
    uniform draw per op picks GET below ``read``, SCAN below ``read +
    scan``, INSERT below ``read + scan + insert`` and update above; an
    update or an INSERT is a PUT.  GETs, updates and SCAN starts draw
    their keys in op order from one Zipfian stream, INSERTs theirs from
    one uniform stream, and SCAN lengths follow the op draws."""
    r = np.random.default_rng(seed)
    u = r.random(n)
    op_types = np.where(u < read, np.uint8(GET), np.uint8(PUT))
    op_types[(u >= read) & (u < read + scan)] = SCAN
    fresh = (u >= read + scan) & (u < read + scan + insert)
    keys = np.empty(n, np.int64)
    keys[fresh] = load_keys(int(np.count_nonzero(fresh)), seed + 1)
    keys[~fresh] = zipf_keys(population, n - int(np.count_nonzero(fresh)),
                             theta, seed + 2)
    scans = op_types == SCAN
    scan_lens = np.zeros(n, np.int32)
    scan_lens[scans] = r.integers(1, max_scan_length + 1,
                                  size=int(np.count_nonzero(scans)))
    return op_types, keys, scan_lens


def load_settle_run(n_load: int, n_run: int, load_rate: float, rate: float,
                    settle_s: float) -> np.ndarray:
    """Arrival times: the load at ``load_rate``, a settle, the run at
    ``rate``."""
    load = np.arange(n_load, dtype=np.float64) / load_rate
    run = load[-1] + settle_s + np.arange(n_run, dtype=np.float64) / rate
    return np.concatenate([load, run])


# ------------------------------------------------------------- one stream
@dataclass
class Stream:
    """One op stream: load then run, with its arrivals, and each SCAN's
    length (None for a stream without SCANs)."""

    op_types: np.ndarray
    keys: np.ndarray
    arrivals: np.ndarray
    n_load: int
    scan_lens: np.ndarray | None = None

    @property
    def n(self) -> int:
        return int(self.op_types.shape[0])

    def mapped(self, key_map: tuple[int, int]) -> "Stream":
        """The same stream over keys ``(a * k + b) mod KEYSPACE``, ``a``
        odd: a bijection of the key space, so the keys stay distinct and
        uniform and the Zipfian hot set keeps its shape, while their order,
        and with it every flush, merge and fence rank, is new.  A mapped
        SCAN start is still a key of the mapped population, so the lengths
        stay as they are."""
        a, b = key_map
        k = self.keys.astype(np.uint64) * np.uint64(a) + np.uint64(b)
        return Stream(self.op_types,
                      (k & np.uint64(KEYSPACE - 1)).astype(np.int64),
                      self.arrivals, self.n_load, self.scan_lens)


@dataclass(frozen=True)
class Traffic:
    """The parameters of one traffic file.  The proportions are YCSB's
    ``readproportion``, ``insertproportion`` and ``scanproportion``
    (updates take the rest); ``max_scan_length`` and
    ``scan_length_distribution`` default to YCSB's core values, and
    ``probe_scans`` is the size of the check's SCAN batch (0 without
    SCANs)."""

    name: str
    operation_count: int
    read_proportion: float
    zipfian_theta: float
    load_rate_ops_s: float
    settle_s: float
    run_rate_ops_s: float
    warm_passes: int
    kernels: tuple[str, ...]
    keep_passes: int
    keep_among: int
    call_sample: float
    probe_keys: int
    limits: dict
    insert_proportion: float = 0.0
    scan_proportion: float = 0.0
    max_scan_length: int = 1000
    scan_length_distribution: str = "uniform"
    probe_scans: int = 0

    @staticmethod
    def from_json(name: str, d: dict) -> "Traffic":
        t = Traffic(
            name=name,
            operation_count=int(d["operation_count"]),
            read_proportion=float(d["read_proportion"]),
            zipfian_theta=float(d["zipfian_theta"]),
            load_rate_ops_s=float(d["load_rate_ops_s"]),
            settle_s=float(d["settle_s"]),
            run_rate_ops_s=float(d["run_rate_ops_s"]),
            warm_passes=int(d["warm_passes"]),
            kernels=tuple(d["kernels"]),
            keep_passes=int(d["check"]["keep_passes"]),
            keep_among=int(d["check"]["keep_among_first"]),
            call_sample=float(d["check"]["call_sample"]),
            probe_keys=int(d["check"]["probe_keys"]),
            limits=dict(d["check"]["limits"]),
            insert_proportion=float(d.get("insert_proportion", 0.0)),
            scan_proportion=float(d.get("scan_proportion", 0.0)),
            max_scan_length=int(d.get("max_scan_length", 1000)),
            scan_length_distribution=str(
                d.get("scan_length_distribution", "uniform")),
            probe_scans=int(d["check"].get("probe_scans", 0)))
        if t.scan_length_distribution != "uniform":
            raise ValueError(f"{name}: scan_length_distribution "
                             f"{t.scan_length_distribution!r}: only "
                             f"'uniform' is generated")
        if (t.read_proportion + t.insert_proportion + t.scan_proportion
                > 1 + 1e-9):
            raise ValueError(f"{name}: the proportions add up to over 1")
        return t

    @property
    def scans(self) -> bool:
        return self.scan_proportion > 0

    def base_stream(self, record_count: int, seed: int) -> Stream:
        """The seed's stream: load, settle, run."""
        pop = load_keys(record_count, seed)
        op_types, keys, scan_lens = ycsb_mix(
            pop, self.operation_count, self.read_proportion,
            self.insert_proportion, self.scan_proportion,
            self.max_scan_length, self.zipfian_theta, seed + 14)
        arrivals = load_settle_run(record_count, self.operation_count,
                                   self.load_rate_ops_s, self.run_rate_ops_s,
                                   self.settle_s)
        lens = None
        if self.scans:
            lens = np.concatenate([np.zeros(record_count, np.int32),
                                   scan_lens])
        return Stream(np.concatenate([np.zeros(record_count, np.uint8),
                                      op_types]),
                      np.concatenate([pop, keys]), arrivals, record_count,
                      lens)


def key_map(seed: int, i: int) -> tuple[int, int]:
    """Pass ``i``'s bijection of the key space, ``(a, b)`` with ``a`` odd,
    drawn from the seed.  The warm-up passes are ``i <= 0``, the window's
    ``1, 2, ...``: no two passes of a run share a map."""
    r = rng(seed, 101, i)
    a = int(r.integers(0, KEYSPACE // 2)) * 2 + 1
    return a, int(r.integers(0, KEYSPACE))
