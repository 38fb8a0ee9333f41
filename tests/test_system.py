"""End-to-end behaviour tests: the paper's headline claims."""

from repro.bench_kv import make_load_a, run_ycsb, sustainable_throughput
from repro.core import LSMConfig

SCALE = 1 << 18


def test_paper_headline_tail_latency():
    """vLSM cuts P99 and max-stall versus RocksDB at 60% of each system's
    sustainable rate (§5 methodology), while chain width shrinks by an
    order of magnitude (paper §6.2)."""
    spec = make_load_a(80_000)
    cfg_v = LSMConfig.vlsm_default(scale=SCALE)
    cfg_r = LSMConfig.rocksdb_default(scale=SCALE)
    v = run_ycsb(cfg_v, spec,
                 0.6 * sustainable_throughput(cfg_v, spec, scale=SCALE),
                 scale=SCALE)
    r = run_ycsb(cfg_r, spec,
                 0.6 * sustainable_throughput(cfg_r, spec, scale=SCALE),
                 scale=SCALE)
    assert v.sim.stats.max_chain_width * 5 < r.sim.stats.max_chain_width
    assert v.sim.stall_max <= r.sim.stall_max
    assert v.sim.p99 <= r.sim.p99


def test_phi64_failure_mode():
    """Fig 13: at Φ=64 (4 MB SSTs) the good-vSST supply collapses."""
    spec = make_load_a(60_000)
    cfg32 = LSMConfig.vlsm_default(scale=SCALE)              # Φ=32
    cfg64 = LSMConfig.vlsm_default(scale=SCALE, sst_frac=16).with_(phi=64)
    r32 = run_ycsb(cfg32, spec, 2500.0, scale=SCALE)
    r64 = run_ycsb(cfg64, spec, 2500.0, scale=SCALE)
    f32 = r32.sim.stats.vssts_good / max(
        1, r32.sim.stats.vssts_good + r32.sim.stats.vssts_poor)
    f64 = r64.sim.stats.vssts_good / max(
        1, r64.sim.stats.vssts_good + r64.sim.stats.vssts_poor)
    assert f32 > f64
