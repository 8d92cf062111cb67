"""Compile the fuse path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a
``v5e:2x2`` topology that is described, not attached, which is what
refuses a kernel Mosaic cannot lower (a 1-D contraction, a block that
breaks the (8, 128) tiling rule) before any chip time is spent.  The
sizes are the real ones: N is the RoBERTa-base encoder body
(``configs/roberta_base.py``, 123,969,792 parameters, bf16) and the codec
block is ``LANE``.  Every compile must contain the Mosaic custom call;
the sharded fuse and the sharded sketch must carry exactly one all-reduce.

The topology is described inside a fixture (never at import time), and
the persistent compilation cache is off around the compiles: an entry
written for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import cold_fuse as CF
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.utils.flat import LANE, SKETCH_BUCKETS, ShardedFlatSpec
from repro.utils.hlo import collect_collectives

N = 123_969_792  # RoBERTa-base encoder body
K = 4
KB = 64  # ContributorClient.submit's default top-k per codec block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
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
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mosaic(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


def test_cold_fuse_compiles_at_roberta_base(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _mosaic(lambda b, c, w: CF.cold_fuse(b, c, w, 1.0, interpret=False),
            s((N,), jnp.bfloat16), s((K, N), jnp.bfloat16), s((K,), jnp.float32))


def test_decode_accum_compiles_at_roberta_base(one_chip):
    nb = -(-N // LANE)
    _mosaic(lambda i, v, w: CF.decode_accum(i, v, w, size=N, block=LANE,
                                            interpret=False),
            jax.ShapeDtypeStruct((2, nb, KB), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((2, nb, KB), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip))


def test_row_sketch_compiles_at_roberta_base(one_chip):
    _mosaic(lambda r: CF.row_sketch(r, SKETCH_BUCKETS, interpret=False),
            jax.ShapeDtypeStruct((N,), jnp.bfloat16, sharding=one_chip))


@pytest.fixture(scope="module")
def mesh4(topo):
    return make_mesh((4,), ("model",), devices=topo.devices)


@pytest.mark.parametrize("cohort", ["dense", "mixed", "compressed"])
def test_sharded_fuse_compiles_with_one_all_reduce(mesh4, cohort):
    """``Repository(mesh=)``'s fuse over the four described chips: Mosaic
    kernels per shard, one psum completing sq_diff, nothing else."""
    sp = ShardedFlatSpec.for_size(N, 4)

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh4, spec))

    base = s((4, sp.shard_len), jnp.bfloat16, P("model", None))
    stage = s((2, 4, sp.shard_len), jnp.bfloat16, P(None, "model", None))
    w = s((2,), jnp.float32, P())
    alpha = s((1,), jnp.float32, P())
    nb = sp.shard_len // LANE
    comp = (s((2, 4, nb, KB), jnp.int16, P(None, "model", None, None)),
            s((2, 4, nb, KB), jnp.int8, P(None, "model", None, None)),
            s((2, 4, nb), jnp.float32, P(None, "model", None)))
    if cohort == "dense":
        fn = ops._sharded_fuse_fn(mesh4, ("model",), True)
        args = (base, stage, w, alpha)
    elif cohort == "mixed":
        fn = ops._compressed_sharded_fn(mesh4, ("model",), LANE, True, True)
        args = (base, *comp, w, stage, w, alpha)
    else:
        fn = ops._compressed_sharded_fn(mesh4, ("model",), LANE, True, False)
        args = (base, *comp, w, alpha)
    stats = collect_collectives(_mosaic(fn, *args))
    assert stats.count_by_kind == {"all-reduce": 1}, stats.count_by_kind


def test_sharded_sketch_compiles_with_one_all_reduce(mesh4):
    """The novelty screen's sharded sketch: one psum completes the
    per-shard partials."""
    sp = ShardedFlatSpec.for_size(N, 4)
    fn = ops._sharded_sketch_fn(mesh4, ("model",), 4, sp.block, SKETCH_BUCKETS)
    row = jax.ShapeDtypeStruct((4, sp.shard_len), jnp.bfloat16,
                               sharding=NamedSharding(mesh4, P("model", None)))
    text = jax.jit(fn).lower(row).compile().as_text()
    stats = collect_collectives(text)
    assert stats.count_by_kind == {"all-reduce": 1}, stats.count_by_kind
