"""Multi-device (8 fake CPU) checks of the SwitchAgg collective dataplane.

Run by tests/test_multidevice.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), \
    "driver must run with fake devices"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as coll
from repro.core import kvagg
from repro.launch.mesh import make_mesh

assert jax.device_count() == 8, jax.device_count()

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))


def check_tree_equals_flat():
    """tree_allreduce == flat psum over (pod,data), for awkward shapes."""
    rng = np.random.default_rng(0)
    for shape in [(64,), (7, 5), (3, 33)]:  # non-divisible sizes hit padding
        x = jnp.asarray(rng.standard_normal((2, 2, *shape)).astype(np.float32))

        def flat(xl):
            return coll.flat_allreduce(xl, ("data", "pod"))

        def tree(xl):
            return coll.tree_allreduce(xl, "data", ("pod",))

        specs = P("pod", "data")
        run = lambda f: jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=specs, out_specs=specs,
            axis_names={"pod", "data"}, check_vma=False))(x)
        a, b = run(flat), run(tree)
        # reduce-scatter+psum reassociates the sum: fp noise only
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    print("tree==flat OK")


def check_compressed_exact_when_k_full():
    """k = full shard and no fpe cap -> compression is lossless."""
    rng = np.random.default_rng(1)
    n = 128
    x = jnp.asarray(rng.standard_normal((2, 2, n)).astype(np.float32))
    res0 = jnp.zeros((8, n // 2), jnp.float32).reshape(2, 2, 2, n // 2)

    def cmp_fn(xl, rl):
        out, nr = coll.tree_compress_allreduce(
            xl.reshape(-1), rl.reshape(-1), "data", ("pod",), k=n // 2,
            fpe_capacity=0)
        return out.reshape(xl.shape), nr.reshape(rl.shape)

    def flat(xl):
        return coll.flat_allreduce(xl, ("data", "pod"))

    got, nr = jax.jit(jax.shard_map(
        cmp_fn, mesh=mesh,
        in_specs=(P("pod", "data"), P("pod", "data", "model")),
        out_specs=(P("pod", "data"), P("pod", "data", "model")),
        axis_names={"pod", "data", "model"}, check_vma=False))(x, res0)
    want = jax.jit(jax.shard_map(
        flat, mesh=mesh, in_specs=P("pod", "data"), out_specs=P("pod", "data"),
        axis_names={"pod", "data"}, check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(nr))) < 1e-6  # nothing left behind
    print("compressed(k=full)==flat OK")


def check_compressed_with_fpe_node():
    """With the bounded-memory FPE node on the pod boundary the result is
    still exact: evictions are BPE-combined and duplicates decompress-add."""
    rng = np.random.default_rng(2)
    n = 128
    x = jnp.asarray(rng.standard_normal((2, 2, n)).astype(np.float32))
    res0 = jnp.zeros((2, 2, 2, n // 2), jnp.float32)

    def cmp_fn(xl, rl):
        out, nr = coll.tree_compress_allreduce(
            xl.reshape(-1), rl.reshape(-1), "data", ("pod",), k=n // 2,
            fpe_capacity=16)  # tiny FPE: heavy eviction path
        return out.reshape(xl.shape), nr.reshape(rl.shape)

    got, _ = jax.jit(jax.shard_map(
        cmp_fn, mesh=mesh,
        in_specs=(P("pod", "data"), P("pod", "data", "model")),
        out_specs=(P("pod", "data"), P("pod", "data", "model")),
        axis_names={"pod", "data", "model"}, check_vma=False))(x, res0)

    def flat(xl):
        return coll.flat_allreduce(xl, ("data", "pod"))

    want = jax.jit(jax.shard_map(
        flat, mesh=mesh, in_specs=P("pod", "data"), out_specs=P("pod", "data"),
        axis_names={"pod", "data"}, check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    print("compressed(fpe=16)==flat OK")


def check_kv_tree_wordcount():
    """The word-count dataplane: 8 workers' KV streams -> root aggregate."""
    rng = np.random.default_rng(3)
    n_per, variety = 256, 64
    keys = rng.integers(0, variety, size=8 * n_per).astype(np.int32)
    vals = np.ones(8 * n_per, np.float32)
    agg = coll.make_kv_tree_aggregator(
        mesh, ("data", "pod"), fpe_capacity=32, ways=4, bpe=True)
    kspec = NamedSharding(mesh, P(("data", "pod")))
    res = agg(jax.device_put(jnp.asarray(keys), kspec),
              jax.device_put(jnp.asarray(vals), kspec))
    # conservation at the root
    got = {}
    for k, v in zip(np.asarray(res.keys).tolist(), np.asarray(res.values).tolist()):
        if k != -1:
            got[k] = got.get(k, 0) + v
    want = {}
    for k in keys.tolist():
        want[k] = want.get(k, 0) + 1.0
    assert got.keys() == want.keys()
    for k in want:
        assert abs(got[k] - want[k]) < 1e-4, (k, got[k], want[k])
    li, lo = np.asarray(res.level_in), np.asarray(res.level_out)
    assert li[0] > 0 and (lo <= li).all()  # every hop reduces (or keeps) traffic
    print(f"kv tree OK: level_in={li.tolist()} level_out={lo.tolist()} "
          f"root_reduction={1 - lo[-1] / li[0]:.3f}")


if __name__ == "__main__":
    check_tree_equals_flat()
    check_compressed_exact_when_k_full()
    check_compressed_with_fpe_node()
    check_kv_tree_wordcount()
    print("ALL OK")
