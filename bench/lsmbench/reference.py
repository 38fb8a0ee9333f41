"""The plain reference: the same semantics as the store, written
independently of it (this module imports nothing of the program).

* A key-value store answers with the latest write: after a stream, each
  key written holds the sequence number of its last write, and a GET
  returns it (``-1`` for a key never written).  Sequence numbers count
  the stream's writes from 0, in stream order.  A SCAN of length ``L``
  from a start key returns the first ``L`` distinct keys written at or
  above the start, in key order, each with the sequence number of its
  last write.
* Reads see writes by memtable window (one tree): the writes of a window
  land before its reads, and a window ends at each op that fills a
  memtable (every ``memtable_size / kv_size``-th write) and at the
  stream's end.
* The device model (the configuration's ``device`` and ``service``
  blocks): a background job holds the device for its bytes read and
  written at the stated bandwidths plus one I/O latency per SST read and
  per SST written (at least one each); at most ``compaction_slots - 1``
  compactions and one flush run at once, and no job starts before the
  jobs it depends on have finished.  A PUT costs ``put_s`` of foreground
  service, a GET ``get_s`` plus one block time per block it reads, each
  block time inflated by ``busy_alpha`` for every compaction running
  when it arrives.  A SCAN costs ``scan_s``, one I/O latency (its seeks
  go out at once), its delivered bytes at the read bandwidth and
  ``scan_file_s`` per file it opens; the blocks it reads add their
  transfer time times ``busy_alpha`` for every compaction running when
  it arrives.  A write stall waits at the op that fills a memtable and
  adds to its service.
* Departures of a FIFO queue follow Lindley's recursion
  ``d_i = max(a_i, d_{i-1}) + s_i`` in float64, the precision the store
  states for its clock; a latency is ``d_i - a_i``.

``departures(..., np.float32)`` is the control: the same recursion one
precision below the stated one.  The per-call references of the kernels
are files of their own, ``bench/references/<name>.py``.
"""

from __future__ import annotations

import numpy as np

PUT = 0
GET = 1
SCAN = 3


def latest_writes(op_types: np.ndarray, keys: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Sorted distinct keys written, and the sequence number of each
    key's last write."""
    w = np.nonzero(op_types == PUT)[0]
    wk = keys[w]
    seq = np.arange(w.shape[0], dtype=np.int64)
    # last occurrence of each key: unique over the reversed writes
    uk, first_rev = np.unique(wk[::-1], return_index=True)
    return uk, seq[::-1][first_rev]


def get_answers(written: tuple[np.ndarray, np.ndarray],
                probe: np.ndarray) -> np.ndarray:
    """What a GET of each probe key returns after the stream."""
    uk, useq = written
    pos = np.searchsorted(uk, probe)
    pos_c = np.minimum(pos, max(uk.shape[0] - 1, 0))
    hit = (pos < uk.shape[0]) & (uk[pos_c] == probe)
    return np.where(hit, useq[pos_c], -1)


def scan_answers(written: tuple[np.ndarray, np.ndarray], starts: np.ndarray,
                 lengths: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """What a SCAN from each start returns after the stream, flattened:
    ``(keys, seqs, offsets)``, scan ``i`` owning
    ``offsets[i]:offsets[i + 1]``."""
    uk, useq = written
    pos = np.searchsorted(uk, np.asarray(starts, np.int64), side="left")
    counts = np.minimum(np.asarray(lengths, np.int64), uk.shape[0] - pos)
    offsets = np.zeros(counts.shape[0] + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    idx = np.repeat(pos - offsets[:-1], counts) + np.arange(offsets[-1])
    return uk[idx], useq[idx], offsets


def scan_delivered(op_types: np.ndarray, keys: np.ndarray,
                   scan_lens: np.ndarray, memtable_keys: int) -> np.ndarray:
    """For each SCAN of the stream, in stream order, how many keys it
    returns: the distinct keys at or above its start that writes up to the
    end of its memtable window have written, at most its length."""
    sc = np.nonzero(op_types == SCAN)[0]
    out = np.zeros(sc.shape[0], np.int64)
    if not sc.shape[0]:
        return out
    fills = fill_ops(op_types, memtable_keys)
    # the last op of each SCAN's window: the next fill, or the stream's end
    last = np.append(fills, op_types.shape[0] - 1)[np.searchsorted(fills, sc)]
    w = np.nonzero(op_types == PUT)[0]
    uk, first = np.unique(keys[w], return_index=True)
    born = w[first]                      # the op that first wrote each key
    for h in np.unique(last):
        mine = last == h
        seen = uk[born <= h]
        pos = np.searchsorted(seen, keys[sc[mine]], side="left")
        out[mine] = np.minimum(scan_lens[sc[mine]], seen.shape[0] - pos)
    return out


def departures(service: np.ndarray, arrivals: np.ndarray,
               dtype=np.float64) -> np.ndarray:
    """Lindley's recursion in closed form:
    ``d_i = C_i + max_{j<=i} (a_j - C_{j-1})`` with ``C`` the running sum
    of service, all in ``dtype``."""
    s = np.asarray(service, dtype)
    c = np.cumsum(s, dtype=dtype)
    base = np.asarray(arrivals, dtype).copy()
    base[1:] -= c[:-1]
    return c + np.maximum.accumulate(base)


def job_seconds(jobs: dict, device: dict) -> np.ndarray:
    """Each job's device time by the device model; ``jobs`` holds arrays
    ``bytes_read``, ``bytes_written``, ``n_in``, ``n_out``."""
    lat = device["io_latency"]
    return (jobs["bytes_read"] / device["read_bw"]
            + np.maximum(1, jobs["n_in"]) * lat
            + jobs["bytes_written"] / device["write_bw"]
            + np.maximum(1, jobs["n_out"]) * lat)


def most_at_once(starts: np.ndarray, ends: np.ndarray) -> int:
    """The most intervals ``[start, end)`` that overlap at one instant."""
    t = np.concatenate([ends, starts])
    step = np.concatenate([-np.ones(ends.shape[0], np.int64),
                           np.ones(starts.shape[0], np.int64)])
    order = np.lexsort((step, t))          # at one instant, ends first
    return int(np.max(np.cumsum(step[order]), initial=0))


def schedule_violations(jobs: dict, device: dict) -> int:
    """Breaches of the device's slots and of the jobs' dependencies:
    compactions or flushes beyond their slots at some instant, plus jobs
    that start before a job they depend on has finished."""
    comp = jobs["compact"]
    over = max(0, most_at_once(jobs["t_start"][comp], jobs["t_finish"][comp])
               - max(1, device["compaction_slots"] - 1))
    over += max(0, most_at_once(jobs["t_start"][~comp],
                                jobs["t_finish"][~comp]) - 1)
    dep, child = jobs["dep"], jobs["dep_of"]
    return over + int(np.count_nonzero(
        jobs["t_start"][child] < jobs["t_finish"][dep]))


def fill_ops(op_types: np.ndarray, memtable_keys: int) -> np.ndarray:
    """The ops that fill a memtable: every ``memtable_keys``-th write."""
    writes = np.nonzero(op_types == PUT)[0]
    return writes[memtable_keys - 1::memtable_keys]


def services(op_types: np.ndarray, arrivals: np.ndarray,
             get_reads: np.ndarray, jobs: dict, stall_ops: np.ndarray,
             stalls: np.ndarray, device: dict, model: dict,
             files: np.ndarray | None = None,
             delivered: np.ndarray | None = None,
             kv_size: int = 0) -> np.ndarray:
    """Each op's foreground service by the device model, given the blocks
    each read op read, the jobs' times and the stalls; for SCANs also the
    files each opened and the keys each delivered (one per SCAN, in
    stream order)."""
    block = device["io_latency"] + device["block_size"] / device["read_bw"]
    comp = jobs["compact"]
    starts = np.sort(jobs["t_start"][comp])
    ends = np.sort(jobs["t_finish"][comp])

    def running(at):
        return (np.searchsorted(starts, at, side="right")
                - np.searchsorted(ends, at, side="right"))

    svc = np.full(op_types.shape[0], model["put_s"], np.float64)
    g = np.nonzero(op_types == GET)[0]
    reads = get_reads[g].astype(np.float64)
    svc[g] = (model["get_s"] + reads * block
              + reads * block * (model["busy_alpha"] * running(arrivals[g])))
    sc = np.nonzero(op_types == SCAN)[0]
    if sc.shape[0]:
        svc[sc] = (model["scan_s"] + device["io_latency"]
                   + delivered * float(kv_size) / device["read_bw"]
                   + files[sc] * model["scan_file_s"]
                   + get_reads[sc] * (device["block_size"] / device["read_bw"])
                   * (model["busy_alpha"] * running(arrivals[sc])))
    np.add.at(svc, stall_ops, stalls)
    return svc
