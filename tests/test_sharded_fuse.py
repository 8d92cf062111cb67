"""Mesh-sharded flat fusion engine (docs/sharding.md): block-cyclic layout
round-trips, sharded-fuse parity against the single-device flat engine and
the per-leaf oracle, the one-all-reduce contract, and Repository(mesh=)
end-to-end semantics.

Tests adapt to whatever device count jax was started with: under plain
pytest that is the single real CPU device (a 1-shard mesh still exercises
the full layout + shard_map path); the CI multi-device smoke re-runs this
file with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  The
subprocess test at the bottom forces 8 fake devices regardless, so tier-1
always covers the real multi-device case once.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.repository import Repository
from repro.kernels import ops
from repro.launch import sharding as SH
from repro.launch.mesh import make_mesh
from repro.utils.flat import LANE, ShardedFlatSpec, flatten_tree
from repro.utils.hlo import collect_collectives

KEY = jax.random.PRNGKey(7)


def _mesh(axis="model"):
    n = jax.device_count()
    return make_mesh((n,), (axis,)), n


def _odd_tree(key, scale=1.0):
    ks = jax.random.split(key, 4)
    return {
        "emb": {"w": jax.random.normal(ks[0], (7, 13)) * scale},
        "blocks": [
            {"w": jax.random.normal(ks[1], (5,)) * scale},
            {"w": jax.random.normal(ks[2], (3, 11, 2)) * scale},
        ],
        "head": jax.random.normal(ks[3], (17,)) * scale,
    }


def _contribs(base, n, seed=0, scale=0.1):
    out = []
    for i in range(n):
        noise = jax.tree.map(
            lambda x, k=jax.random.fold_in(jax.random.PRNGKey(seed), i):
                jax.random.normal(k, x.shape, jnp.float32) * scale,
            base)
        out.append(jax.tree.map(jnp.add, base, noise))
    return out


# ---------------------------------------------------------------------------
# ShardedFlatSpec: the block-cyclic layout itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 5, 561, LANE, 9000])
@pytest.mark.parametrize("s", [1, 2, 8])
def test_layout_roundtrip(n, s):
    sp = ShardedFlatSpec.for_size(n, s)
    assert sp.block % LANE == 0
    assert sp.padded_size % s == 0 and sp.padded_size >= n
    x = jnp.arange(n, dtype=jnp.float32)
    sh = sp.shard(x)
    assert sh.shape == (s, sp.shard_len)
    np.testing.assert_array_equal(np.asarray(sp.unshard(sh)), np.asarray(x))


def test_layout_block_cyclic_placement():
    """Element i lives on shard (i // B) % S — consecutive blocks round-robin
    across shards, and shard_of agrees with the actual rearrangement."""
    sp = ShardedFlatSpec(size=10 * LANE + 7, n_shards=4, block=LANE)
    x = jnp.arange(sp.size, dtype=jnp.float32)
    sh = np.asarray(sp.shard(x))
    for i in (0, LANE - 1, LANE, 5 * LANE + 3, sp.size - 1):
        s, off = sp.shard_of(i)
        assert s == (i // sp.block) % sp.n_shards
        assert sh[s, off] == float(i)


def test_layout_padding_is_zero():
    sp = ShardedFlatSpec.for_size(LANE + 1, 2)
    sh = np.asarray(sp.shard(jnp.ones((sp.size,))))
    assert sh.sum() == sp.size  # every non-payload slot is exactly 0


def test_layout_batch_dims():
    sp = ShardedFlatSpec.for_size(777, 4)
    x = jnp.arange(3 * 777, dtype=jnp.float32).reshape(3, 777)
    sh = sp.shard(x)
    assert sh.shape == (3, 4, sp.shard_len)
    np.testing.assert_array_equal(np.asarray(sp.unshard(sh)), np.asarray(x))


def test_layout_errors():
    with pytest.raises(ValueError):
        ShardedFlatSpec.for_size(10, 0)
    with pytest.raises(ValueError):
        ShardedFlatSpec.for_size(10, 2, block=100)  # not LANE-aligned
    sp = ShardedFlatSpec.for_size(10, 2)
    with pytest.raises(ValueError):
        sp.shard(jnp.ones((11,)))
    with pytest.raises(ValueError):
        sp.unshard(jnp.ones((3, sp.shard_len)))
    with pytest.raises(ValueError):
        sp.shard_of(10)


def test_layout_balanced_regardless_of_leaves():
    tree = _odd_tree(KEY)
    _, spec = flatten_tree(tree)
    sp = ShardedFlatSpec.from_spec(spec, 8)
    assert sp.shard_len * 8 == sp.padded_size  # equal slice per shard


# ---------------------------------------------------------------------------
# sharded fuse vs the single-device flat engine and the per-leaf oracle
# ---------------------------------------------------------------------------


def _sharded_inputs(base, contribs, mesh, axes, sp):
    bsh = jax.device_put(sp.shard(base), SH.flat_row_sharding(mesh, axes))
    csh = jax.device_put(sp.shard(contribs), SH.flat_stage_sharding(mesh, axes))
    return bsh, csh


@pytest.mark.parametrize("alpha", [1.0, 0.3])
def test_sharded_vs_flat_engine(alpha):
    mesh, s = _mesh()
    N, K = 100_003, 5
    base = jax.random.normal(KEY, (N,))
    contribs = jnp.stack(
        [base + 0.01 * jax.random.normal(jax.random.fold_in(KEY, i), (N,))
         for i in range(K)])
    w = jnp.asarray([1.0, 2.0, 0.5, 1.0, 3.0])
    sp = ShardedFlatSpec.for_size(N, s)
    bsh, csh = _sharded_inputs(base, contribs, mesh, "model", sp)
    want_f, want_sq = ops.fuse_flat(base, contribs, w, alpha)
    got_f, got_sq = ops.fuse_flat_sharded(bsh, csh, w, alpha, mesh=mesh, axes="model")
    np.testing.assert_allclose(
        np.asarray(sp.unshard(got_f)), np.asarray(want_f), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_sq), np.asarray(want_sq), rtol=1e-4)


def test_sharded_zero_weight_masks_nonfinite_row():
    """The screen's re-weighted second pass relies on weight-0 rows being
    masked out entirely — shard-locally, since w/Σw is shard-invariant."""
    mesh, s = _mesh()
    N = 3000
    base = jax.random.normal(KEY, (N,))
    contribs = jnp.concatenate(
        [jnp.stack([base + 1.0, base - 1.0]), jnp.full((1, N), jnp.nan)])
    w = jnp.asarray([1.0, 1.0, 0.0])
    sp = ShardedFlatSpec.for_size(N, s)
    bsh, csh = _sharded_inputs(base, contribs, mesh, "model", sp)
    fused, sq = ops.fuse_flat_sharded(bsh, csh, w, 1.0, mesh=mesh, axes="model")
    np.testing.assert_allclose(
        np.asarray(sp.unshard(fused)), np.asarray(base), atol=1e-5)
    assert not np.isfinite(np.asarray(sq)[2])  # statistic still honest


def test_sharded_fuse_exactly_one_all_reduce():
    """The paper's limited-communication budget: one psum per fuse, no
    hidden gathers of the staging buffer."""
    mesh, s = _mesh()
    N, K = 40_000, 4
    base = jax.random.normal(KEY, (N,))
    contribs = jnp.stack([base + 0.1 * (i + 1) for i in range(K)])
    sp = ShardedFlatSpec.for_size(N, s)
    bsh, csh = _sharded_inputs(base, contribs, mesh, "model", sp)
    fn = ops._sharded_fuse_fn(mesh, ("model",), False)
    hlo = fn.lower(bsh, csh, jnp.ones((K,), jnp.float32),
                   jnp.ones((1,), jnp.float32)).compile().as_text()
    stats = collect_collectives(hlo)
    assert stats.count_by_kind.get("all-reduce", 0) == 1, stats.count_by_kind
    assert stats.count_by_kind.get("all-gather", 0) == 0, stats.count_by_kind


# ---------------------------------------------------------------------------
# Repository(mesh=)
# ---------------------------------------------------------------------------


def test_repository_mesh_matches_all_engines():
    """Sharded == single-device flat == per-leaf oracle, for a cohort with a
    screened-out NaN contributor (exercises the re-weighted second pass)."""
    mesh, _ = _mesh()
    base = _odd_tree(KEY)
    ups = _contribs(base, 4)
    ups.append(jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), base))
    repos = {
        "mesh": Repository(base, mesh=mesh),
        "flat": Repository(base, use_flat=True),
        "leaf": Repository(base, use_flat=False),
    }
    recs = {}
    for name, repo in repos.items():
        for u in ups:
            repo.upload(u)
        recs[name] = repo.fuse_pending()
    assert recs["mesh"].n_accepted == recs["flat"].n_accepted == 4
    np.testing.assert_allclose(
        recs["mesh"].diff_norms, recs["flat"].diff_norms, rtol=1e-4)
    for other in ("flat", "leaf"):
        for a, b in zip(jax.tree.leaves(repos["mesh"].download()),
                        jax.tree.leaves(repos[other].download())):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op,kw", [
    ("average", {}),
    ("damped", {"alpha": 0.4}),
    ("task_arithmetic", {"lam": 0.3}),
])
def test_repository_mesh_all_operators(op, kw):
    mesh, _ = _mesh()
    base = _odd_tree(KEY)
    ups = _contribs(base, 3, scale=0.05)
    rm = Repository(base, mesh=mesh, fusion_op=op, fusion_kwargs=kw, screen=False)
    rf = Repository(base, use_flat=False, fusion_op=op, fusion_kwargs=kw, screen=False)
    for u in ups:
        rm.upload(u)
        rf.upload(u)
    rm.fuse_pending()
    rf.fuse_pending()
    for a, b in zip(jax.tree.leaves(rm.download()), jax.tree.leaves(rf.download())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_repository_mesh_stages_rows_sharded():
    """upload must place each row straight into its shard layout — the
    staging buffer grows on the mesh, not on one device."""
    mesh, s = _mesh()
    base = _odd_tree(KEY)
    repo = Repository(base, mesh=mesh)
    repo.upload(_contribs(base, 1)[0])
    row = repo._pending[0]
    assert row.ndim == 2 and row.shape[0] == s
    assert row.sharding == SH.flat_row_sharding(mesh, repo.mesh_axes)
    rec = repo.fuse_pending()
    assert rec.n_accepted == 1
    # the fused flat base stays sharded between iterations
    assert repo._base_flat.sharding == SH.flat_row_sharding(mesh, repo.mesh_axes)


def test_repository_mesh_spill_roundtrip(tmp_path):
    """With mesh= the spill files hold per-shard slices (the sharded spill
    layout); the fuse over spilled rows matches the in-memory flat engine."""
    from repro.checkpoint import io as ckpt

    mesh, _ = _mesh()
    root = str(tmp_path / "repo")
    base = _odd_tree(KEY)
    ups = _contribs(base, 3)
    rm = Repository(base, mesh=mesh, root=root, spill=True)
    rp = Repository(base, use_flat=True)
    for u in ups:
        rm.upload(u)
        rp.upload(u)
    assert all(isinstance(p, str) and os.path.exists(p) for p in rm._pending)
    assert all(ckpt.is_flat_sharded(p) for p in rm._pending)
    rm.fuse_pending()
    rp.fuse_pending()
    for a, b in zip(jax.tree.leaves(rm.download()), jax.tree.leaves(rp.download())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_repository_mesh_sharded_spill_recovery_no_full_row(tmp_path, monkeypatch):
    """Crash recovery of sharded spill re-stages each row shard by shard:
    the reload path must never reassemble a full [N] row on the host."""
    from repro.checkpoint import io as ckpt
    from repro.utils import flat as F

    mesh, _ = _mesh()
    root = str(tmp_path / "repo")
    base = _odd_tree(KEY)
    ups = _contribs(base, 3)
    rm = Repository(base, mesh=mesh, root=root, spill=True)
    for u in ups:
        rm.upload(u)
    # "crash": drop the in-memory repository; reopen under the same mesh
    # with every full-row path forbidden
    def boom(*a, **k):
        raise AssertionError("full [N] row materialized on host")
    monkeypatch.setattr(F.ShardedFlatSpec, "unshard_slices", boom)
    monkeypatch.setattr(ckpt.FlatShardReader, "full_row", boom)
    monkeypatch.setattr(ckpt, "load_flat", boom)
    again = Repository.open(root, mesh=mesh, spill=True)
    assert len(again._pending) == 3
    rec = again.fuse_pending()
    monkeypatch.undo()
    assert rec.n_accepted == 3
    rp = Repository(base, use_flat=True)
    for u in ups:
        rp.upload(u)
    rp.fuse_pending()
    for a, b in zip(jax.tree.leaves(again.download()), jax.tree.leaves(rp.download())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_repository_mesh_sharded_spill_reopens_meshless(tmp_path):
    """Portability fallback: a sharded spill reopened WITHOUT a mesh
    reassembles rows on the host and still fuses correctly."""
    mesh, _ = _mesh()
    root = str(tmp_path / "repo")
    base = _odd_tree(KEY)
    ups = _contribs(base, 2)
    rm = Repository(base, mesh=mesh, root=root, spill=True)
    for u in ups:
        rm.upload(u)
    again = Repository.open(root, use_flat=True, spill=False)
    assert len(again._pending) == 2
    rec = again.fuse_pending()
    assert rec.n_accepted == 2


def test_repository_mesh_async_and_rollback():
    mesh, _ = _mesh()
    base = _odd_tree(KEY)
    c = _contribs(base, 1)[0]
    rm = Repository(base, mesh=mesh, keep_history=True)
    rf = Repository(base, use_flat=True, keep_history=True)
    rm.contribute_async(c, alpha=0.5)
    rf.contribute_async(c, alpha=0.5)
    for a, b in zip(jax.tree.leaves(rm.download()), jax.tree.leaves(rf.download())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    rm.rollback(0)  # clears _base_flat; next fuse re-shards from the pytree
    for u in _contribs(base, 2):
        rm.upload(u)
    assert rm.fuse_pending().n_accepted == 2


def test_repository_mesh_requires_flat_engine():
    mesh, _ = _mesh()
    with pytest.raises(ValueError, match="flat engine"):
        Repository(_odd_tree(KEY), mesh=mesh, use_flat=False)
    with pytest.raises(ValueError, match="flat engine"):
        Repository(_odd_tree(KEY), mesh=mesh, fusion_op="ties")
    with pytest.raises(ValueError, match="mesh_axes"):
        Repository(_odd_tree(KEY), mesh=mesh, mesh_axes=("nope",))


def test_repository_mesh_forces_flat_even_without_kernels():
    mesh, _ = _mesh()
    prev = ops.kernels_enabled()
    ops.use_kernels(False)
    try:
        repo = Repository(_odd_tree(KEY), mesh=mesh)
        assert repo.use_flat  # shard_map path is plain XLA, no kernels needed
    finally:
        ops.use_kernels(prev)


# ---------------------------------------------------------------------------
# the shared mesh-level path (make_fuse_step)
# ---------------------------------------------------------------------------


def test_cohort_fuse_sharded_matches_per_leaf():
    """ops.cohort_fuse_sharded == the per-leaf mean/lerp oracle, for both
    plain and damped fusion, on a contrib-only mesh."""
    mesh = make_mesh((jax.device_count(),), ("contrib",))
    C, N = 2 * jax.device_count(), 5000  # slabs divide the contributor axis
    buf = jax.random.normal(KEY, (C, N))
    for alpha in (1.0, 0.3):
        mean = jnp.mean(buf, axis=0, keepdims=True)
        want = buf * (1 - alpha) + mean * alpha
        sp = ShardedFlatSpec.for_size(N, 1)
        got = ops.cohort_fuse_sharded(
            sp.shard(buf), mesh=mesh, contrib_axes="contrib",
            shard_axes=(), alpha=alpha)
        np.testing.assert_allclose(
            np.asarray(sp.unshard(got)), np.asarray(want), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# forced 8-device end-to-end (subprocess, like test_distributed.py)
# ---------------------------------------------------------------------------

SCRIPT_8DEV = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from repro.core.repository import Repository
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.utils.flat import ShardedFlatSpec
from repro.utils.hlo import collect_collectives
from repro.launch import sharding as SH

assert jax.device_count() == 8
mesh = make_mesh((8,), ("model",))

def tree(key, scale=1.0):
    ks = jax.random.split(key, 3)
    return {"a": jax.random.normal(ks[0], (37, 13)) * scale,
            "b": [jax.random.normal(ks[1], (251,)) * scale,
                  jax.random.normal(ks[2], (3, 11, 2)) * scale]}

base = tree(jax.random.PRNGKey(0))
ups = [jax.tree.map(lambda x, k=jax.random.fold_in(jax.random.PRNGKey(1), i):
                    x + 0.05 * jax.random.normal(k, x.shape), base)
       for i in range(5)]
ups.append(jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), base))

rm = Repository(base, mesh=mesh)
rf = Repository(base, use_flat=True)
rl = Repository(base, use_flat=False)
for u in ups:
    rm.upload(u); rf.upload(u); rl.upload(u)
st = rm._pending[0]
assert st.shape[0] == 8 and st.sharding == SH.flat_row_sharding(mesh, rm.mesh_axes)
recs = [r.fuse_pending() for r in (rm, rf, rl)]
assert all(r.n_accepted == 5 for r in recs), [r.n_accepted for r in recs]
np.testing.assert_allclose(recs[0].diff_norms, recs[1].diff_norms, rtol=1e-4)
for other in (rf, rl):
    for a, b in zip(jax.tree.leaves(rm.download()), jax.tree.leaves(other.download())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)

# one-all-reduce contract on the real 8-device mesh
N, K = 50_000, 4
b = jax.random.normal(jax.random.PRNGKey(2), (N,))
c = jnp.stack([b + 0.1 * (i + 1) for i in range(K)])
sp = ShardedFlatSpec.for_size(N, 8)
bsh = jax.device_put(sp.shard(b), SH.flat_row_sharding(mesh, ("model",)))
csh = jax.device_put(sp.shard(c), SH.flat_stage_sharding(mesh, ("model",)))
fn = ops._sharded_fuse_fn(mesh, ("model",), False)
hlo = fn.lower(bsh, csh, jnp.ones((K,), jnp.float32),
               jnp.ones((1,), jnp.float32)).compile().as_text()
stats = collect_collectives(hlo)
assert stats.count_by_kind.get("all-reduce", 0) == 1, stats.count_by_kind
fused, sq = fn(bsh, csh, jnp.ones((K,), jnp.float32), jnp.ones((1,), jnp.float32))
want_f, want_sq = ops.fuse_flat(b, c, jnp.ones((K,), jnp.float32), 1.0)
np.testing.assert_allclose(np.asarray(sp.unshard(fused)), np.asarray(want_f),
                           rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(sq), np.asarray(want_sq), rtol=1e-4)
print("SHARDED-8DEV-OK")
'''


@pytest.mark.slow
def test_sharded_fuse_8_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT_8DEV], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), env=env,
        timeout=900,
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "SHARDED-8DEV-OK" in res.stdout
