# Pallas TPU kernels for the perf-critical compute layers.
#
# Paper hot-spots:
#   merge_path    — compaction sorted-run merge (merge-path diagonal tiling)
#   overlap_scan  — §4.2 per-key L2-fence overlap probes (batched counts)
#   lindley_scan  — DES FIFO-queue departure recursion (blocked max-plus
#                   scan, batched over shards / sweep points)
#
# Each subpackage ships kernel.py (pl.pallas_call + BlockSpec), ops.py
# (jit'd wrapper) and ref.py (pure-jnp oracle).  platform.py decides the
# mode: compiled on a TPU, interpreted elsewhere (the CPU tests).  Import
# lazily — these pull in jax.
