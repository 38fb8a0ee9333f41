"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Prints ``name,value,derived`` CSV — one section per paper table/figure
(Figs 1-13, Table 1).  Use ``--full`` for the larger op counts,
``--only fig08,fig13`` to select.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated figure ids (default: all)")
    ap.add_argument("--full", action="store_true",
                    help="larger op counts (slower, smoother tails)")
    ap.add_argument("--json", default=None,
                    help="also persist every emitted row as JSON here")
    ap.add_argument("--policy", default="all",
                    help="compaction policy name(s) for the db_bench "
                         "section, comma-separated, or 'all' — resolved "
                         "from the repro.core.policies registry")
    ap.add_argument("--seed", type=int, default=7,
                    help="base RNG seed for the db_bench-backed sections")
    ap.add_argument("--workers", type=int, default=1,
                    help="sweep-executor fork-pool size for the "
                         "fleet_sweep section (1 = in-process; rows are "
                         "byte-identical at every worker count)")
    ap.add_argument("--sanitize", action="store_true",
                    help="run every simulation under the DES schedule "
                         "sanitizer (REPRO_SANITIZE=1; see "
                         "docs/analysis.md) — slower, but any scheduling "
                         "invariant violation aborts at first divergence")
    args = ap.parse_args()
    if args.sanitize:
        import os
        os.environ["REPRO_SANITIZE"] = "1"
    from repro.kernels.platform import enable_compile_cache
    enable_compile_cache()

    from . import fig_benchmarks as fb
    names = args.only.split(",") if args.only else list(fb.ALL)
    t0 = time.time()
    print("name,value,derived")
    for name in names:
        fn = fb.ALL[name]
        t1 = time.time()
        if args.full:
            try:
                fn(120_000)          # larger op count where supported
            except TypeError:
                fn()
        else:
            fn()
        print(f"# {name} done in {time.time()-t1:.1f}s", flush=True)
    # db_bench (paper §5: amplification-only, Meta-style population).
    # Policies resolve from the registry: --policy vlsm,lazy or 'all'.
    from repro.bench_kv.db_bench import chain_report, fill_sim, fillrandom
    from repro.core.policies import get_policy, resolve_names
    from .common import SCALE, emit
    chosen = resolve_names(args.policy)
    for dist in ("uniform", "pareto"):
        for nm in chosen:
            cfg = get_policy(nm).default_config(scale=SCALE)
            run = fill_sim(cfg, 60_000, dist, SCALE, args.seed)
            row = fillrandom(cfg, 60_000, dist=dist, scale=SCALE,
                             seed=args.seed, run=run)
            emit(f"db_bench.{dist}.io_amp.{nm}", row["io_amp"],
                 f"levels={row['levels_filled']}")
            if dist != "uniform":
                continue
            # chain observatory off the SAME simulation (paper §3;
            # full distributions live in db_bench's chain_report
            # rows — see docs/benchmarks.md)
            crow = chain_report(cfg, 60_000, scale=SCALE,
                                seed=args.seed, run=run)
            emit(f"db_bench.chain.mean_width_ssts.{nm}",
                 crow.get("mean_width_ssts", 0.0),
                 f"eff_len={crow.get('effective_length', 0.0)}")
    # sharded fleet: P99 vs shard count at a fixed aggregate rate, plus
    # the Zipf hot-shard interference point (full distributions live in
    # db_bench's shard_sweep rows — see docs/benchmarks.md)
    from repro.bench_kv.db_bench import (HOT_RATE, HOT_SHARDS,
                                         SHARD_COUNTS, SWEEP_RATE,
                                         shard_sweep)
    for nm in resolve_names(args.policy):
        for k in SHARD_COUNTS:
            cfg = get_policy(nm).default_config(scale=SCALE) \
                .with_(n_shards=k)
            row = shard_sweep(cfg, 20_000, 30_000, scale=SCALE,
                              rate=SWEEP_RATE, seed=args.seed)
            emit(f"db_bench.shard_sweep.p99_get_ms.{nm}.x{k}",
                 row["p99_get_ms"], f"p999={row['p999_get_ms']}")
        cfg = get_policy(nm).default_config(scale=SCALE) \
            .with_(n_shards=HOT_SHARDS, shard_router="range")
        row = shard_sweep(cfg, 20_000, 30_000, dist="zipf_ranked",
                          scale=SCALE, rate=HOT_RATE, seed=args.seed)
        emit(f"db_bench.shard_hot.p99_get_ms.{nm}.x{HOT_SHARDS}",
             row["p99_get_ms"],
             f"hot_frac={row['hot_shard_frac']};"
             f"stall_s={row['stall_total_s']}")
    # batched fleet engine: the policy × shard × rate matrix as one
    # structural replay per point + batched Lindley accounting, with the
    # serial heap loop as timed baseline and parity oracle (full-size
    # matrix lives in db_bench's fleet_sweep rows — see docs/benchmarks.md)
    from repro.bench_kv.db_bench import (FLEET_RATES_QUICK,
                                         fleet_sweep_bench)
    frows = fleet_sweep_bench(resolve_names(args.policy), 6_000, 8_000,
                              scale=SCALE, rates=FLEET_RATES_QUICK,
                              shard_counts=(1, 4), seed=args.seed,
                              workers=args.workers)
    summary = frows[-1]
    emit("db_bench.fleet_sweep.speedup", summary["speedup"],
         f"runs={summary['runs']};"
         f"fleet_wall_s={summary['fleet_wall_s']}")
    emit("db_bench.fleet_sweep.parity_max_abs_latency_s",
         summary["parity_max_abs_latency_s"],
         f"stalls_equal={summary['parity_stalls_equal']}")
    top_rate = max(r["rate_ops_s"] for r in frows[:-1])
    for row in frows[:-1]:
        if row["rate_ops_s"] == top_rate:
            emit(f"db_bench.fleet_sweep.p99_get_ms."
                 f"{row['policy']}.x{row['n_shards']}",
                 row["p99_get_ms"], f"rate={row['rate_ops_s']}")
    # open-loop multi-tenant serving: goodput/shed/priority-tail numbers
    # at and past the saturation knee, admission off vs on (full
    # per-factor rows live in db_bench's serve_sweep output — see
    # docs/benchmarks.md)
    from .serving_tail import bench_serving_tail
    bench_serving_tail(120_000 if args.full else 60_000)
    print(f"# total {time.time()-t0:.1f}s")
    if args.json:
        import json
        from pathlib import Path

        from repro.analysis.schemas import (CSV_FAMILY,
                                            paranoid_validate_rows)

        from .common import ROWS
        # schema gate (no-op unless REPRO_PARANOID_CHECKS=1): rows
        # must match the shape repro-lint extracts from common.emit
        paranoid_validate_rows(ROWS, family=CSV_FAMILY)
        Path(args.json).write_text(json.dumps(ROWS, indent=1))
        print(f"# wrote {args.json} ({len(ROWS)} rows)")


if __name__ == "__main__":
    main()
