"""Compile the store's three Pallas kernels for a TPU v5e that is
described, not attached, at the largest shapes ``chip_smoke.py`` reaches
(vLSM at 1 << 26 bytes, 2.2M ops).  Mosaic refuses here what interpret
mode accepts: unaligned ref slices, oversized VMEM, unsupported ops.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU compiler's library, and every test worker
imports this file.
"""

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_a,n_b", [(1024, 1024), (65536, 524288),
                                     (524288, 524288), (65536, 262144)])
def test_merge_path_compiles_for_v5e(one_chip, n_a, n_b):
    """A compaction merge: the largest smoke merge is one L1 group
    (~47k keys) against an L2 slice of up to ~330k keys; the vLSM cell's
    merges are one 41,943-key SST (bucket 65,536) against an L1 group."""
    from repro.kernels.merge_path.kernel import BLOCK, merge_path_call
    a = _shape(one_chip, (3, n_a // BLOCK + 1, 8, 128), jnp.int32)
    b = _shape(one_chip, (3, n_b // BLOCK + 1, 8, 128), jnp.int32)
    _assert_kernel(merge_path_call.lower(a, b, interpret=False).compile())


@pytest.mark.parametrize("n_fences,n_keys", [(128, 1024), (1024, 65536)])
def test_fence_rank_compiles_for_v5e(one_chip, n_fences, n_keys):
    """A GET window's manifest ranks: the smoke's windows hold ~15k GETs
    against at most 38 fences per level."""
    from repro.kernels.overlap_scan import fence_rank_call
    f = _shape(one_chip, (n_fences,), jnp.int32)
    k = _shape(one_chip, (n_keys // 128, 128), jnp.int32)
    _assert_kernel(fence_rank_call.lower(f, f, k, k,
                                         interpret=False).compile())


@pytest.mark.parametrize("rows,n", [(1, 1 << 22), (16, 1 << 16)])
def test_lindley_scan_compiles_for_v5e(one_chip, rows, n):
    """The final departure scan: one 2.2M-op queue pads to 2^22."""
    from repro.kernels.lindley_scan import lindley_scan_call
    p = _shape(one_chip, (rows, n // 128, 128), jnp.float32)
    d0 = _shape(one_chip, (2 * rows,), jnp.float32)
    _assert_kernel(lindley_scan_call.lower(d0, p, p, p, p,
                                           interpret=False).compile())

