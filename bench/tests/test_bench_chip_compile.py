"""Compile the four-chip cell's fuse for a described TPU v5e 2x2, at the
cell's own size: the configuration file's N and the traffic's K.

Nothing runs: the TPU compiler installed with JAX compiles for a topology
that is described, not attached.  The sharded fuse ``Repository(mesh=)``
dispatches must keep its Mosaic kernel, carry exactly one all-reduce, and
fit a chip's 16 GB.  The topology is described inside a fixture (never at
import time), and the persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back
without one.  Here, not in ``tests/test_chip_compile.py``, because it
reads the cell's sizes from the benchmark's files.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import harness

CELL = "inproc.granite-moe-1b-a400m"
HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        # take no libtpu lock file: tests/test_chip_compile.py describes
        # the same topology, maybe at once in another process, and one
        # that finds the lock held skips
        mp.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.fixture(scope="module")
def mesh4(topo):
    from repro.launch.mesh import make_mesh

    return make_mesh((4,), ("model",), devices=topo.devices)


def test_sharded_fuse_compiles_at_the_cells_size(mesh4):
    from repro.kernels import ops
    from repro.utils.flat import ShardedFlatSpec
    from repro.utils.hlo import collect_collectives

    cell = harness.resolve(harness.benchmark(), CELL)
    n = cell["config_file"]["body_parameters"]
    k = cell["traffic_file"]["contributors"]
    assert cell["chips"] == 4
    sp = ShardedFlatSpec.for_size(n, 4)

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh4, spec))

    fn = ops._sharded_fuse_fn(mesh4, ("model",), True)
    compiled = jax.jit(fn).lower(
        s((4, sp.shard_len), jnp.bfloat16, P("model", None)),
        s((k, 4, sp.shard_len), jnp.bfloat16, P(None, "model", None)),
        s((k,), jnp.float32, P()), s((1,), jnp.float32, P())).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    stats = collect_collectives(text)
    assert stats.count_by_kind == {"all-reduce": 1}, stats.count_by_kind
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # the stage, the base and the fused base: (K + 2) shards of N / 4
    assert per_chip >= (k + 2) * sp.shard_len * 2
    assert per_chip < HBM, per_chip
