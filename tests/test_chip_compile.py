"""Compiles of the main path's kernels for a described TPU v5e.

No chip is attached: the TPU compiler builds for a ``v5e:2x2`` topology
that is only described, so Mosaic's refusals (tiling, VMEM, SMEM layouts)
show up here instead of on the chip.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test worker imports this file.  Keep every such compile in this one
file, so that the single worker given it holds the library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import kvagg
from repro.kernels.kv_aggregate import fpe_aggregate_pallas

#: a switch-scale FPE table: 65,536 pairs in 4-way buckets
CAPACITY, WAYS, BLOCK_N = 1 << 16, 4, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.mark.parametrize("op,lanes", [("sum", ()), ("mean", (2,))])
def test_fpe_kernel_compiles_for_v5e(op, lanes, one_chip,
                                     no_persistent_cache):
    n = 1 << 16
    keys = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((n, *lanes), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda k, v: fpe_aggregate_pallas(
        k, v, capacity=CAPACITY, ways=WAYS, op=op, block_n=BLOCK_N,
        interpret=False)).lower(keys, vals).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    # evictions and table, keys plus value lanes, none padded to 128 lanes
    out = compiled.memory_analysis().output_size_in_bytes
    assert out < 1.01 * 4 * (n + CAPACITY) * (1 + max(lanes, default=1))


#: the group maps of the stream cell's shapes: a BPE over a 32,767-record
#: batch's [n + capacity] eviction stream, and the fast-path FPE itself
GROUP_MAP_PROGRAMS = {
    "sorted_combine": (
        lambda k, v: kvagg.sorted_combine(k, v, op="sum"),
        ((32767 + CAPACITY,), jnp.int32), ((32767 + CAPACITY,), jnp.float32)),
    "fpe_fast": (
        lambda k, v, tk, tv: kvagg.fpe_aggregate(
            k, v, capacity=CAPACITY, ways=WAYS, op="sum",
            exact_stream=False, table_keys=tk, table_values=tv),
        ((32767,), jnp.int32), ((32767,), jnp.float32),
        ((CAPACITY,), jnp.int32), ((CAPACITY,), jnp.float32)),
}


@pytest.mark.parametrize("program", sorted(GROUP_MAP_PROGRAMS))
def test_group_maps_compile_without_loops_for_v5e(program, one_chip,
                                                  no_persistent_cache):
    """Lowered for the TPU, each record finds its group from the sort: no
    ``while`` (the CPU's binary searches) is left in the program, while the
    same program compiled for the CPU keeps its search loops."""
    fn, *shapes = GROUP_MAP_PROGRAMS[program]
    tpu = jax.jit(fn).lower(*(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                              for s, d in shapes)).compile()
    assert "while(" not in tpu.as_text()
    cpu = jax.jit(fn).lower(*(jax.ShapeDtypeStruct(s, d)
                              for s, d in shapes)).compile()
    assert "while(" in cpu.as_text()


def test_fast_path_over_int32_limit_raises():
    """n * capacity at the int32 limit: a ValueError naming it, never a
    silent switch to the serial scan."""
    n = (2**31 - 1) // CAPACITY + 1
    keys = jnp.zeros((n,), jnp.int32)
    with pytest.raises(ValueError, match="int32 limit"):
        kvagg.fpe_aggregate(keys, jnp.ones((n,), jnp.float32),
                            capacity=CAPACITY, ways=WAYS, exact_stream=False)
    ok = kvagg.fpe_aggregate(keys[:n - 1], jnp.ones((n - 1,), jnp.float32),
                             capacity=CAPACITY, ways=WAYS, exact_stream=False)
    assert int(jnp.sum(ok.table_keys == 0)) == 1
