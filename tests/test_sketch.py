"""Novelty admission sketch (docs/service_loop.md): row_sketch kernel vs
the jnp oracle vs the host twin, block-cyclic shard partials summing to the
portable-row sketch for arbitrary layouts, the one-psum contract of the
sharded path, CohortSketch distance/window/JSON semantics, and Repository
persistence + recovery of the cohort sketch state.

Like tests/test_sharded_fuse.py, mesh tests adapt to whatever device count
jax was started with (a 1-shard mesh still exercises the full shard_map
path); scripts/ci.sh re-runs this file under the forced 8-fake-device
config."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt
from repro.core.repository import SKETCH_FILE, Repository
from repro.kernels import ops, ref
from repro.kernels.cold_fuse import row_sketch as kernel_row_sketch
from repro.launch.mesh import make_mesh
from repro.utils.flat import (LANE, CohortSketch, ShardedFlatSpec,
                              row_sketch_host)
from repro.utils.hlo import collect_collectives

KEY = jax.random.PRNGKey(11)


def _row(n, seed=0, scale=1.0):
    return jax.random.normal(jax.random.fold_in(KEY, seed), (n,),
                             jnp.float32) * scale


# ---------------------------------------------------------------------------
# kernel / oracle / host-twin parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [5, 1000, LANE, 3 * LANE + 7, 70_000])
@pytest.mark.parametrize("n_buckets", [4, 32])
def test_row_sketch_kernel_matches_oracle(n, n_buckets):
    row = _row(n)
    want = np.asarray(ref.row_sketch(row, n_buckets))
    got = np.asarray(kernel_row_sketch(row, n_buckets, block=4 * LANE))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert got.shape == (2, n_buckets) and got.dtype == np.float32


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_row_sketch_host_twin_matches_oracle(dtype):
    row = _row(9000).astype(dtype)
    want = np.asarray(ref.row_sketch(row, 16))
    got = row_sketch_host(np.asarray(row), 16)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-2)


def test_row_sketch_padding_invariant():
    """Zero padding contributes nothing: a row and its zero-extension
    sketch identically (the property that makes the sketch layout- and
    padding-independent)."""
    row = _row(2 * LANE + 3)
    ext = jnp.concatenate([row, jnp.zeros((5 * LANE - row.shape[0],))])
    np.testing.assert_allclose(np.asarray(ref.row_sketch(row, 8)),
                               np.asarray(ref.row_sketch(ext, 8)), atol=1e-4)


@pytest.mark.parametrize("s", [1, 2, 4, 8])
@pytest.mark.parametrize("n", [LANE - 5, 4 * LANE + 17, 40_000])
def test_shard_partials_sum_to_portable_sketch(s, n):
    """Host-side check of the psum contract for several layouts: the S
    per-shard partials of a block-cyclic row sum to the [N] row's sketch."""
    row = _row(n, seed=3)
    sp = ShardedFlatSpec.for_size(n, s)
    parts = [np.asarray(ref.row_sketch_shard(jnp.asarray(sl), i, s,
                                             sp.block, 8))
             for i, sl in enumerate(sp.shard_slices(np.asarray(row)))]
    np.testing.assert_allclose(np.sum(parts, axis=0),
                               np.asarray(ref.row_sketch(row, 8)),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# sharded ops path: parity with the single-device oracle + one all-reduce
# ---------------------------------------------------------------------------


def _mesh(axis="model"):
    n = jax.device_count()
    return make_mesh((n,), (axis,)), n


def test_row_sketch_sharded_matches_single_device():
    mesh, s = _mesh()
    n = 6 * LANE + 123
    row = _row(n, seed=5)
    sp = ShardedFlatSpec.for_size(n, s)
    placed = jax.device_put(sp.shard(row),
                            jax.sharding.NamedSharding(
                                mesh, jax.sharding.PartitionSpec("model", None)))
    got = ops.row_sketch_sharded(placed, mesh=mesh, axes=("model",),
                                 block=sp.block)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ops.row_sketch(row)),
                               rtol=1e-5, atol=1e-3)


def test_row_sketch_sharded_single_all_reduce():
    """The comm contract of docs/sharding.md extends to the sketch: one
    psum per sketch, nothing else."""
    mesh, s = _mesh()
    sp = ShardedFlatSpec.for_size(16 * LANE, s)
    sh = sp.shard(_row(16 * LANE))
    fn = ops._sharded_sketch_fn(mesh, ("model",), s, sp.block, 32)
    hlo = fn.lower(sh).compile().as_text()
    stats = collect_collectives(hlo)
    assert stats.count_by_kind.get("all-reduce", 0) <= 1, stats.count_by_kind
    assert stats.count_by_kind.get("all-gather", 0) == 0, stats.count_by_kind


# ---------------------------------------------------------------------------
# CohortSketch: distance semantics, window, JSON round trip
# ---------------------------------------------------------------------------


def _sketch_of(row):
    return np.asarray(ref.row_sketch(jnp.asarray(row), 16))


def test_cohort_sketch_duplicate_vs_distinct():
    n = 4 * LANE
    base = np.zeros((n,), np.float32)
    a = np.asarray(_row(n, seed=1, scale=0.1)) + 1.0
    dup = a + 1e-6
    other = np.asarray(_row(n, seed=2, scale=0.1)) + 2.0
    sk = CohortSketch(n, 16, window=8)
    sk.set_base(_sketch_of(base))
    sa, sd, so = _sketch_of(a), _sketch_of(dup), _sketch_of(other)
    assert sk.distance(sa, sa) == 0.0
    assert sk.distance(sa, sd) < 1e-4 < 0.05 < sk.distance(sa, so)
    sk.add("a", sa, file="a.npz")
    assert sk.match(sd, 0.05) is not None          # replay caught
    assert sk.match(so, 0.05) is None              # novelty admitted
    # self-match skip demands id AND file: the crash re-screen is exempt,
    # a forged-id replay under a different queue file is not
    assert sk.match(sa, 0.05, skip_id="a", skip_file="a.npz") is None
    assert sk.match(sa, 0.05, skip_id="a", skip_file="b.npz") is not None
    assert sk.match(sa, 0.05, skip_id="a") is not None
    hit = sk.match(sd, 0.05)
    assert hit[0] == "a" and hit[1] < 1e-4


def test_cohort_sketch_scale_relative():
    """The threshold is scale-free: scaling base + rows together does not
    change relative distances (up to float error)."""
    n = 2 * LANE
    base = np.asarray(_row(n, seed=7))
    a, b = base + 0.01, base + 0.02
    for scale in (1.0, 1000.0):
        sk = CohortSketch(n, 16, window=4)
        sk.set_base(_sketch_of(base * scale))
        d = sk.distance(_sketch_of(a * scale), _sketch_of(b * scale))
        np.testing.assert_allclose(d, 0.5, rtol=1e-3)


def test_cohort_sketch_window_and_idempotent_add():
    sk = CohortSketch(LANE, 4, window=2)
    s = [np.full((2, 4), float(i)) for i in range(4)]
    sk.add("a", s[0])
    sk.add("a", s[1])          # same id replaces, not duplicates
    assert len(sk) == 1
    sk.add("b", s[2])
    sk.add("c", s[3])          # window=2: "a" trimmed
    assert [e[0] for e in sk.entries] == ["b", "c"]
    sk.discard("b")
    assert [e[0] for e in sk.entries] == ["c"]
    sk.discard("nope")         # absent id is a no-op
    with pytest.raises(ValueError, match="window"):
        CohortSketch(LANE, 4, window=0)
    with pytest.raises(ValueError, match="shape"):
        sk.add("d", np.zeros((3, 3)))


def test_cohort_sketch_json_roundtrip():
    n = 2 * LANE + 9
    sk = CohortSketch(n, 8, window=3)
    sk.set_base(np.asarray(ref.row_sketch(jnp.zeros((n,)), 8)))
    row = np.asarray(_row(n, seed=9)) + 1.0
    sk.add("x", np.asarray(ref.row_sketch(jnp.asarray(row), 8)))
    sk2 = CohortSketch.from_json(sk.to_json())
    assert (sk2.size, sk2.n_buckets, sk2.window) == (n, 8, 3)
    assert sk2.match(np.asarray(ref.row_sketch(jnp.asarray(row + 1e-7), 8)),
                     0.05) is not None
    np.testing.assert_allclose(sk2.base, sk.base)


# ---------------------------------------------------------------------------
# Repository integration: persistence, publish refresh, open recovery
# ---------------------------------------------------------------------------


def _m(v, n=2000):
    return {"w": jnp.full((n,), float(v)), "b": jnp.full((7,), float(v))}


def test_repository_sketch_persist_and_reopen(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=False)
    sk = repo.enable_cohort_sketch(window=4)
    assert os.path.exists(os.path.join(root, SKETCH_FILE))
    assert sk.base is not None
    sk.add("s0", repo._sketch_of_staged(repo._spec.flatten(_m(1.0))))
    repo.save_cohort_sketch()
    again = Repository.open(root, spill=True)
    assert again.cohort_sketch is not None and len(again.cohort_sketch) == 1
    # enable with a smaller window adopts + trims, larger keeps entries
    adopted = again.enable_cohort_sketch(window=8)
    assert adopted is again.cohort_sketch and len(adopted) == 1
    assert adopted.window == 8


def test_repository_sketch_refreshes_base_at_publish(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=False)
    repo.enable_cohort_sketch(window=4)
    before = np.array(repo.cohort_sketch.base)
    repo.upload(_m(3.0))
    repo.fuse_pending()
    after = np.array(repo.cohort_sketch.base)
    assert not np.allclose(before, after)  # base moved, normalizer follows
    on_disk = CohortSketch.from_json(
        ckpt.load_json(os.path.join(root, SKETCH_FILE)))
    np.testing.assert_allclose(on_disk.base, after)


def test_repository_sketch_row_file_matches_direct(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=False)
    repo.enable_cohort_sketch(window=4)
    spec = repo._spec
    row = spec.flatten(_m(5.0))
    p = os.path.join(root, "queue", "q-000000.npz")
    ckpt.save_flat(p, np.asarray(row), spec)
    got = repo.sketch_row_file(p)
    np.testing.assert_allclose(got, np.asarray(ops.row_sketch(row)),
                               rtol=1e-5, atol=1e-3)
    # sharded file through the same entry point (portable fallback)
    sspec = ShardedFlatSpec.from_spec(spec, 4)
    p2 = os.path.join(root, "queue", "q-000001.npz")
    ckpt.save_flat_shards(p2, sspec.shard_slices(np.asarray(row)), spec, sspec)
    np.testing.assert_allclose(repo.sketch_row_file(p2), got,
                               rtol=1e-5, atol=1e-3)


def test_per_leaf_reopen_keeps_sketch_dormant(tmp_path):
    """A repository reopened on the per-leaf engine with a recovered
    sketch must not touch it (or crash) at publish — the history stays
    intact for the next flat-engine run."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=False)
    sk = repo.enable_cohort_sketch(window=4)
    sk.add("x", repo._sketch_of_staged(repo._spec.flatten(_m(1.0))))
    repo.save_cohort_sketch()
    with pytest.warns(UserWarning, match="per-leaf"):
        leafy = Repository.open(root, use_flat=False, screen=False)
    assert leafy.cohort_sketch is not None
    leafy.upload(_m(2.0))
    leafy.fuse_pending()  # publish on the per-leaf engine: sketch untouched
    assert len(leafy.cohort_sketch) == 1
    on_disk = CohortSketch.from_json(
        ckpt.load_json(os.path.join(root, SKETCH_FILE)))
    assert len(on_disk) == 1


def test_repository_ignores_mismatched_sketch_file(tmp_path):
    root = str(tmp_path / "repo")
    Repository(_m(0), root=root, spill=True, screen=False)
    ckpt.save_json_atomic(os.path.join(root, SKETCH_FILE),
                          CohortSketch(123, 8, 4).to_json())
    with pytest.warns(UserWarning, match="N=123"):
        again = Repository.open(root, spill=True)
    assert again.cohort_sketch is None
