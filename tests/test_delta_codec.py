"""Delta-compressed contributions (docs/service_loop.md): codec round-trip
error bounds (fuzzed), torn-file rejection at every byte offset, edge-case
geometry, the Pallas decode+accumulate kernel against its jnp oracle, the
compressed fuse against the dense fuse, the sharded variant's one-psum
contract, the sketch-from-delta twin, and the Repository's mixed-cohort
dispatch.

Mesh tests adapt to whatever device count jax was started with (a 1-shard
mesh still exercises the full shard_map path); scripts/ci.sh re-runs this
file under the forced 8-fake-device config."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt
from repro.core.repository import Repository
from repro.kernels import ops, ref
from repro.kernels.cold_fuse import decode_accum as kernel_decode_accum
from repro.launch.mesh import make_mesh
from repro.utils.flat import (LANE, MAX_DELTA_BLOCK, DeltaPayload, FlatSpec,
                              ShardedFlatSpec, delta_checksum, delta_decode,
                              delta_decode_sharded, delta_encode,
                              delta_encode_sharded, delta_entries,
                              row_sketch_host, sketch_apply_delta)
from repro.utils.hlo import collect_collectives

from _hypothesis_compat import given, settings, st  # noqa: E402

KEY = jax.random.PRNGKey(23)


def _row(n, seed=0, scale=1.0):
    return np.asarray(jax.random.normal(jax.random.fold_in(KEY, seed), (n,),
                                        jnp.float32)) * np.float32(scale)


def _mesh(axis="model"):
    n = jax.device_count()
    return make_mesh((n,), (axis,)), n


# ---------------------------------------------------------------------------
# codec round trip: fuzzed error bounds
# ---------------------------------------------------------------------------


@given(
    n=st.integers(1, 3 * LANE + 200),
    kb=st.integers(0, 96),
    seed=st.integers(0, 10_000),
    scale=st.floats(min_value=float(np.float32(1e-3)), max_value=100.0, width=32),
)
@settings(max_examples=20, deadline=None)
def test_roundtrip_error_bounds(n, kb, seed, scale):
    """For every block: kept entries reconstruct within half a quantization
    step; dropped entries are zero in the decode and no larger than the
    smallest kept magnitude (top-k selection)."""
    base = _row(n, seed=seed + 1)
    row = base + _row(n, seed=seed + 2, scale=scale)
    pay = delta_encode(row, base, k_per_block=kb)
    dec = delta_decode(pay, base)
    assert dec.shape == (n,) and dec.dtype == np.float32
    d = (row - base).astype(np.float32)
    err = np.abs(dec - base - d)
    nb, block = pay.n_blocks, pay.block
    pad = np.zeros((nb * block,), np.float32)
    pad[:n] = d
    d_blocks = pad.reshape(nb, block)
    for b in range(nb):
        mags = np.sort(np.abs(d_blocks[b]))[::-1]
        # kb=0 keeps nothing: the bound is the block's own max magnitude
        min_kept = mags[kb - 1] if kb else (mags[0] if mags.size else 0.0)
        bound = max(pay.scales[b] / 2.0, min_kept) * (1 + 1e-5) + 1e-7
        e = err[b * block:(b + 1) * block]
        assert e.size == 0 or e.max() <= bound, (b, e.max(), bound)
    # sq statistic of the decoded delta exceeds the true delta's by at most
    # the half-step rounding of each kept entry
    dv = np.zeros((nb * block,), np.float32)
    gi, vv = delta_entries(pay)
    np.add.at(dv, gi, vv)
    kept_bound = np.sum(np.square(np.abs(pad[gi]) + pay.scales[gi // block] / 2))
    assert np.sum(dv * dv) <= kept_bound * (1 + 1e-5) + 1e-7


@given(n=st.integers(1, 2 * LANE + 50), seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_roundtrip_dense_k_is_halfstep_exact(n, seed):
    """kb=block keeps every entry: the only error is quantization, bounded
    by scale/2 everywhere."""
    base = _row(n, seed=seed)
    row = base + _row(n, seed=seed + 7, scale=0.5)
    pay = delta_encode(row, base, k_per_block=LANE)
    dec = delta_decode(pay, base)
    step = np.repeat(pay.scales, pay.block)[:n]
    assert np.all(np.abs(dec - row) <= step / 2 * (1 + 1e-5) + 1e-7)


def test_roundtrip_bit_exact_with_representable_values():
    """Integer base values and 1/256-grid deltas make the scale an exact
    power of two — the decode is then bit-for-bit."""
    rng = np.random.default_rng(3)
    n = 2 * LANE + 64
    base = rng.integers(-3, 4, n).astype(np.float32)
    d = np.zeros(n, np.float32)
    d[::5] = np.float32(127 / 256.0)
    d[1::9] = np.float32(-64 / 256.0)
    row = base + d
    pay = delta_encode(row, base, k_per_block=LANE)
    assert np.array_equal(delta_decode(pay, base), row)


# ---------------------------------------------------------------------------
# torn files: reject at every truncation offset, never stall or mis-decode
# ---------------------------------------------------------------------------


def test_truncation_at_every_byte_offset_rejects(tmp_path):
    base = np.zeros((LANE,), np.float32)
    row = _row(LANE, seed=4, scale=0.1)
    spec = FlatSpec.from_tree({"w": jnp.asarray(row)})
    pay = delta_encode(row, base, k_per_block=8)
    path = str(tmp_path / "full.npz")
    ckpt.save_flat_delta(path, pay, spec, extra={"base_iteration": 0})
    blob = open(path, "rb").read()
    torn = str(tmp_path / "torn.npz")
    for cut in range(len(blob)):
        with open(torn, "wb") as f:
            f.write(blob[:cut])
        with pytest.raises(Exception):
            ckpt.load_flat_delta(torn)
    # the intact file still loads after all that
    payloads, meta = ckpt.load_flat_delta(path)
    assert meta["compressed"] and len(payloads) == 1
    np.testing.assert_array_equal(payloads[0].indices, pay.indices)


def test_flipped_payload_geometry_rejects(tmp_path):
    """Entries present but inconsistent (a corrupted-in-place file) raise
    from DeltaPayload validation, not a silent mis-decode."""
    row = _row(LANE, seed=5, scale=0.1)
    spec = FlatSpec.from_tree({"w": jnp.asarray(row)})
    pay = delta_encode(row, np.zeros((LANE,), np.float32), k_per_block=4)
    path = str(tmp_path / "x.npz")
    ckpt.save_flat_delta(path, pay, spec)
    with np.load(path) as data:
        arrays = {k: data[k] for k in data.files}
    arrays["__delta_indices__"] = arrays["__delta_indices__"][:, :2]
    np.savez(path, **arrays)
    with pytest.raises(Exception):
        ckpt.load_flat_delta(path)


# ---------------------------------------------------------------------------
# edge cases + payload validation
# ---------------------------------------------------------------------------


def test_k_zero_and_all_zero_delta():
    n = LANE + 33
    base = _row(n, seed=6)
    p0 = delta_encode(base + 1.0, base, k_per_block=0)
    assert p0.k_per_block == 0 and p0.nbytes < 64
    np.testing.assert_array_equal(delta_decode(p0, base), base)
    pz = delta_encode(base.copy(), base, k_per_block=16)
    assert np.all(pz.scales == 0.0) and np.all(pz.values == 0)
    np.testing.assert_array_equal(delta_decode(pz, base), base)
    gi, dv = delta_entries(pz)
    assert gi.size == 0 and dv.size == 0


def test_encode_validation():
    base = np.zeros((LANE,), np.float32)
    with pytest.raises(ValueError, match="finite"):
        delta_encode(np.full((LANE,), np.nan, np.float32), base,
                     k_per_block=4)
    with pytest.raises(ValueError):
        delta_encode(base, base, k_per_block=4, block=LANE + 1)  # not LANE-mult
    with pytest.raises(ValueError):
        delta_encode(base, base, k_per_block=4, block=2 * MAX_DELTA_BLOCK)


def test_payload_validation_rejects_bad_arrays():
    good = delta_encode(np.ones((LANE,), np.float32),
                        np.zeros((LANE,), np.float32), k_per_block=4)
    with pytest.raises(ValueError):
        DeltaPayload(good.indices.astype(np.int32), good.values, good.scales,
                     good.size, good.block)
    with pytest.raises(ValueError):
        DeltaPayload(good.indices, good.values.astype(np.int16), good.scales,
                     good.size, good.block)
    bad_idx = good.indices.copy()
    bad_idx[0, 0] = good.block  # out of range
    with pytest.raises(ValueError):
        DeltaPayload(bad_idx, good.values, good.scales, good.size, good.block)
    with pytest.raises(ValueError):
        DeltaPayload(good.indices, good.values, good.scales[:-1].copy()
                     if good.scales.size > 1 else
                     np.zeros((0,), np.float32), good.size, good.block)


def test_delta_checksum_sensitivity():
    base = np.zeros((2 * LANE,), np.float32)
    pay = delta_encode(_row(2 * LANE, seed=8, scale=0.2) + base, base,
                       k_per_block=8)
    want = delta_checksum(pay)
    assert want == delta_checksum([pay]) and len(want) == 8
    v = pay.values.copy()
    v[0, 0] ^= 1
    assert delta_checksum(
        DeltaPayload(pay.indices, v, pay.scales, pay.size, pay.block)) != want
    s = pay.scales.copy()
    s[0] *= np.float32(1.0000001)
    assert delta_checksum(
        DeltaPayload(pay.indices, pay.values, s, pay.size, pay.block)) != want
    i = pay.indices.copy()
    i[0, 0] += 1
    assert delta_checksum(
        DeltaPayload(i, pay.values, pay.scales, pay.size, pay.block)) != want


# ---------------------------------------------------------------------------
# sharded codec round trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s", [1, 2, 8])
def test_sharded_encode_decode_roundtrip(s):
    n = 6 * LANE + 123
    base = _row(n, seed=9)
    row = base + _row(n, seed=10, scale=0.3)
    sp = ShardedFlatSpec.for_size(n, s)
    pays = delta_encode_sharded(row, base, sp, k_per_block=LANE)
    assert len(pays) == sp.n_shards
    dec = delta_decode_sharded(pays, sp, base)
    whole = delta_decode(delta_encode(row, base, k_per_block=LANE), base)
    # per-shard and whole-row paths quantize block-by-block identically
    # (the shard slices are block-aligned), so the decodes agree exactly
    np.testing.assert_array_equal(dec, whole)


# ---------------------------------------------------------------------------
# decode_accum: Pallas kernel vs jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c,nb,kb", [(1, 1, 4), (3, 4, 32), (5, 2, LANE)])
def test_decode_accum_kernel_matches_oracle(c, nb, kb):
    rng = np.random.default_rng(11)
    block = LANE
    idx = rng.integers(0, block, (c, nb, kb)).astype(np.int16)
    dv = rng.standard_normal((c, nb, kb)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, c).astype(np.float32)
    w[0] = 0.0  # zero-weight masking is part of the contract
    size = nb * block - 37
    want_acc, want_sq = ref.decode_accum(
        jnp.asarray(idx), jnp.asarray(dv), jnp.asarray(w),
        size=size, block=block)
    got_acc, got_sq = kernel_decode_accum(
        jnp.asarray(idx, jnp.int32), jnp.asarray(dv), jnp.asarray(w),
        size=size, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got_acc), np.asarray(want_acc),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_sq), np.asarray(want_sq),
                               rtol=1e-5, atol=1e-5)


def test_decode_accum_duplicate_offsets_accumulate():
    idx = np.zeros((1, 1, 4), np.int16)  # all four entries hit element 0
    dv = np.full((1, 1, 4), 0.25, np.float32)
    acc, sq = ref.decode_accum(jnp.asarray(idx), jnp.asarray(dv),
                               jnp.ones((1,)), size=LANE, block=LANE)
    assert float(acc[0]) == 1.0 and float(jnp.sum(jnp.abs(acc[1:]))) == 0.0
    np.testing.assert_allclose(float(sq[0]), 4 * 0.25 ** 2)


def test_ops_decode_accum_empty_cohort():
    acc, sq = ops.decode_accum(
        np.zeros((0, 1, 4), np.int16), np.zeros((0, 1, 4), np.int8),
        np.zeros((0, 1), np.float32), np.zeros((0,), np.float32),
        size=LANE, block=LANE)
    assert acc.shape == (LANE,) and sq.shape == (0,)


# ---------------------------------------------------------------------------
# compressed fuse == dense fuse
# ---------------------------------------------------------------------------


def _compressed_cohort(n, c, seed=20, k_per_block=LANE, scale=0.3):
    base = _row(n, seed=seed)
    rows = [base + _row(n, seed=seed + 1 + i, scale=scale) for i in range(c)]
    pays = [delta_encode(r, base, k_per_block=k_per_block) for r in rows]
    decoded = [delta_decode(p, base) for p in pays]
    return base, pays, decoded


def test_fuse_flat_compressed_matches_dense_fuse():
    n, c = 3 * LANE + 137, 3
    base, pays, decoded = _compressed_cohort(n, c)
    wc = jnp.asarray([1.0, 2.0, 0.5], jnp.float32)
    idx = np.stack([p.indices for p in pays])
    val = np.stack([p.values for p in pays])
    scl = np.stack([p.scales for p in pays])
    fused_c, sq_c = ops.fuse_flat_compressed(
        jnp.asarray(base), idx, val, scl, wc, 1.0, block=LANE)
    fused_d, sq_d = ops.fuse_flat(
        jnp.asarray(base), jnp.stack([jnp.asarray(r) for r in decoded]), wc)
    np.testing.assert_allclose(np.asarray(fused_c), np.asarray(fused_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sq_c), np.asarray(sq_d),
                               rtol=1e-4, atol=1e-4)


def test_fuse_flat_compressed_mixed_matches_dense_fuse():
    n = 2 * LANE + 99
    base, pays, decoded = _compressed_cohort(n, 2, seed=30)
    dense = np.stack([base + _row(n, seed=40 + i, scale=0.2)
                      for i in range(2)])
    wd = jnp.asarray([2.0, 0.0], jnp.float32)  # zero weight masked
    wc = jnp.asarray([1.0, 3.0], jnp.float32)
    fused_c, sq_c = ops.fuse_flat_compressed(
        jnp.asarray(base),
        np.stack([p.indices for p in pays]),
        np.stack([p.values for p in pays]),
        np.stack([p.scales for p in pays]),
        wc, 1.0, block=LANE, dense=jnp.asarray(dense), dense_weights=wd)
    all_rows = jnp.concatenate(
        [jnp.asarray(dense), jnp.stack([jnp.asarray(r) for r in decoded])])
    fused_d, sq_d = ops.fuse_flat(
        jnp.asarray(base), all_rows, jnp.concatenate([wd, wc]))
    np.testing.assert_allclose(np.asarray(fused_c), np.asarray(fused_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sq_c), np.asarray(sq_d),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# sharded compressed fuse: parity + the one-psum contract
# ---------------------------------------------------------------------------


def _sharded_setup(n, c, seed=50):
    mesh, s = _mesh()
    sp = ShardedFlatSpec.for_size(n, s)
    base = _row(n, seed=seed)
    rows = [base + _row(n, seed=seed + 1 + i, scale=0.25) for i in range(c)]
    pays = [delta_encode_sharded(r, base, sp, k_per_block=64) for r in rows]
    idx = np.stack([[q.indices for q in pl] for pl in pays])
    val = np.stack([[q.values for q in pl] for pl in pays])
    scl = np.stack([[q.scales for q in pl] for pl in pays])
    decoded = [delta_decode_sharded(pl, sp, base) for pl in pays]
    return mesh, sp, base, (idx, val, scl), decoded


def test_fuse_flat_compressed_sharded_matches_single_device():
    n, c = 6 * LANE + 123, 3
    mesh, sp, base, (idx, val, scl), decoded = _sharded_setup(n, c)
    wc = jnp.asarray([1.0, 0.5, 2.0], jnp.float32)
    fused_sh, sq_sh = ops.fuse_flat_compressed_sharded(
        sp.shard(base), idx, val, scl, wc, 1.0,
        mesh=mesh, axes=("model",), block=LANE)
    fused_1d, sq_1d = ops.fuse_flat(
        jnp.asarray(base), jnp.stack([jnp.asarray(r) for r in decoded]), wc)
    np.testing.assert_allclose(np.asarray(sp.unshard(fused_sh)),
                               np.asarray(fused_1d), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sq_sh), np.asarray(sq_1d),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("has_dense", [False, True])
def test_fuse_flat_compressed_sharded_single_all_reduce(has_dense):
    """The docs/sharding.md comm contract holds for the compressed fuse:
    exactly ONE all-reduce (the concatenated sq partials), no all-gather —
    the fused output needs no communication at all."""
    n, c = 8 * LANE, 2
    mesh, sp, base, (idx, val, scl), _ = _sharded_setup(n, c, seed=60)
    wc = jnp.ones((c,), jnp.float32)
    alpha = jnp.ones((1,), jnp.float32)
    fn = ops._compressed_sharded_fn(mesh, ("model",), LANE, False, has_dense)
    if has_dense:
        dense = jnp.stack([sp.shard(base)])
        wd = jnp.ones((1,), jnp.float32)
        hlo = fn.lower(sp.shard(base), idx, val, scl, wc, dense, wd,
                       alpha).compile().as_text()
    else:
        hlo = fn.lower(sp.shard(base), idx, val, scl, wc,
                       alpha).compile().as_text()
    stats = collect_collectives(hlo)
    assert stats.count_by_kind.get("all-reduce", 0) <= 1, stats.count_by_kind
    assert stats.count_by_kind.get("all-gather", 0) == 0, stats.count_by_kind


# ---------------------------------------------------------------------------
# sketch from delta: matches the dense sketch twin
# ---------------------------------------------------------------------------


def test_sketch_apply_delta_matches_dense_sketch():
    n = 5 * LANE + 77
    base = _row(n, seed=70)
    pay = delta_encode(base + _row(n, seed=71, scale=0.4), base,
                       k_per_block=48)
    decoded = delta_decode(pay, base)
    gi, dv = delta_entries(pay)
    got = sketch_apply_delta(row_sketch_host(base), gi, dv, base[gi])
    want = row_sketch_host(decoded)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# on-disk format + Repository mixed-cohort dispatch
# ---------------------------------------------------------------------------


def test_save_load_flat_delta_roundtrip(tmp_path):
    n = 2 * LANE + 11
    row = _row(n, seed=80, scale=0.2)
    spec = FlatSpec.from_tree({"w": jnp.asarray(row)})
    base = np.zeros((n,), np.float32)
    pay = delta_encode(row, base, k_per_block=16)
    p = str(tmp_path / "c.npz")
    ckpt.save_flat_delta(p, pay, spec, extra={"base_iteration": 3})
    assert ckpt.is_flat_compressed(p) and not ckpt.is_flat(p)
    meta = ckpt.flat_row_meta(p)
    assert meta["compressed"] and not meta["sharded"]
    assert meta["delta_spec"]["k_per_block"] == 16
    assert meta["extra"]["base_iteration"] == 3
    loaded, _ = ckpt.load_flat_delta(p)
    np.testing.assert_array_equal(loaded[0].indices, pay.indices)
    np.testing.assert_array_equal(loaded[0].values, pay.values)
    # dense loaders refuse it rather than return garbage
    with pytest.raises(Exception):
        ckpt.load_flat(p)


def _m(v, n=3 * LANE + 137):
    return {"w": jnp.full((n,), float(v), jnp.float32)}


def _ingest_compressed(repo, qdir, name, delta_value, weight,
                       base_iteration=None, k_per_block=LANE):
    spec = repo._spec
    n = spec.size
    base = np.asarray(repo.flat_base_host())
    pay = delta_encode(base + np.float32(delta_value), base,
                       k_per_block=k_per_block)
    p = os.path.join(qdir, name)
    it = repo.iteration if base_iteration is None else base_iteration
    ckpt.save_flat_delta(p, pay, spec, extra={"base_iteration": it})
    repo.ingest_spilled(p, weight=weight, meta=ckpt.flat_row_meta(p))
    return p


def test_repository_mixed_cohort_closed_form(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0.0), root=root, spill=True, fusion_op="average")
    repo._ensure_flat_base()
    qd = os.path.join(root, "queue")
    os.makedirs(qd)
    spec = repo._spec
    for i, (v, w) in enumerate([(1.0, 2.0), (3.0, 1.0)]):
        p = os.path.join(qd, f"d{i}.npz")
        ckpt.save_flat(p, np.full(spec.size, v, np.float32), spec)
        repo.ingest_spilled(p, weight=w)
    _ingest_compressed(repo, qd, "c0.npz", 5.0, 1.0)
    _ingest_compressed(repo, qd, "c1.npz", 7.0, 2.0)
    rec = repo.fuse_pending(wait=True)
    want = (2 * 1.0 + 1 * 3.0 + 1 * 5.0 + 2 * 7.0) / 6.0
    np.testing.assert_allclose(np.asarray(repo.flat_base_host()), want,
                               atol=1e-5)
    assert rec.n_contributions == 4 and rec.n_accepted == 4
    # diff_norms came back in COHORT order (dense, dense, comp, comp)
    np.testing.assert_allclose(
        rec.diff_norms, [np.sqrt(spec.size) * v for v in (1, 3, 5, 7)],
        rtol=1e-4)


def test_repository_screen_zeroes_compressed_outlier(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0.0), root=root, spill=True, fusion_op="average",
                      mad_threshold=3.0)
    repo._ensure_flat_base()
    qd = os.path.join(root, "queue")
    os.makedirs(qd)
    spec = repo._spec
    for i in range(3):
        p = os.path.join(qd, f"d{i}.npz")
        ckpt.save_flat(p, np.full(spec.size, 1.0, np.float32), spec)
        repo.ingest_spilled(p)
    _ingest_compressed(repo, qd, "outlier.npz", 500.0, None)
    rec = repo.fuse_pending(wait=True)
    assert rec.n_contributions == 4 and rec.n_accepted == 3
    np.testing.assert_allclose(np.asarray(repo.flat_base_host()), 1.0,
                               atol=1e-5)


def test_repository_stale_compressed_recovery_skips(tmp_path):
    """A compressed manifest entry whose declared vintage disagrees with
    the reopened repository is skipped with a warning — never decoded
    against the wrong base."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0.0), root=root, spill=True, screen=False)
    repo._ensure_flat_base()
    qd = os.path.join(root, "queue")
    os.makedirs(qd)
    _ingest_compressed(repo, qd, "c0.npz", 1.0, None, base_iteration=0)
    # publish once WITHOUT consuming (simulate divergence): hand-advance
    # the recorded iteration as a hand-edited-state stand-in
    repo.iteration = 2
    repo._persist_base()
    with repo._manifest_lock:
        repo._write_manifest()
    with pytest.warns(UserWarning, match="encoded against base iteration"):
        again = Repository.open(root, spill=True)
    assert again.n_staged == 0


def test_repository_sketch_delta_file_matches_dense(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0.0), root=root, spill=True, screen=False)
    repo.enable_cohort_sketch(window=4)
    spec = repo._spec
    qd = os.path.join(root, "queue")
    os.makedirs(qd)
    base = np.asarray(repo.flat_base_host())
    row = base + _row(spec.size, seed=90, scale=0.3)
    pay = delta_encode(row, base, k_per_block=32)
    p = os.path.join(qd, "c.npz")
    ckpt.save_flat_delta(p, pay, spec, extra={"base_iteration": 0})
    got = repo.sketch_delta_file(p)
    want = row_sketch_host(delta_decode(pay, base))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)
    # the generic entry point routes compressed files the same way
    np.testing.assert_allclose(repo.sketch_row_file(p), got, atol=1e-6)


def test_repository_sharded_mixed_cohort(tmp_path):
    mesh, s = _mesh()
    root = str(tmp_path / "repo")
    repo = Repository(_m(0.0), root=root, spill=True, screen=False,
                      mesh=mesh)
    repo._ensure_flat_base()
    spec, sspec = repo._spec, repo._sspec
    qd = os.path.join(root, "queue")
    os.makedirs(qd)
    p = os.path.join(qd, "d0.npz")
    ckpt.save_flat_shards(
        p, sspec.shard_slices(np.full(spec.size, 2.0, np.float32)),
        spec, sspec)
    repo.ingest_spilled(p, weight=1.0)
    base = np.asarray(repo.flat_base_host())
    pays = delta_encode_sharded(base + np.float32(6.0), base, sspec,
                                k_per_block=LANE)
    p = os.path.join(qd, "c0.npz")
    ckpt.save_flat_delta(p, pays, spec, sspec=sspec,
                         extra={"base_iteration": 0})
    repo.ingest_spilled(p, weight=3.0, meta=ckpt.flat_row_meta(p))
    repo.fuse_pending(wait=True)
    np.testing.assert_allclose(np.asarray(repo.flat_base_host()),
                               (1 * 2.0 + 3 * 6.0) / 4.0, atol=1e-5)


def test_repository_whole_row_payload_on_mesh_falls_back(tmp_path):
    """A whole-row compressed payload on a sharded repository host-decodes
    to a dense row (slow path) instead of failing."""
    mesh, s = _mesh()
    root = str(tmp_path / "repo")
    repo = Repository(_m(0.0), root=root, spill=True, screen=False,
                      mesh=mesh)
    repo._ensure_flat_base()
    spec = repo._spec
    qd = os.path.join(root, "queue")
    os.makedirs(qd)
    base = np.asarray(repo.flat_base_host())
    pay = delta_encode(base + np.float32(4.0), base, k_per_block=LANE)
    p = os.path.join(qd, "c0.npz")
    ckpt.save_flat_delta(p, pay, spec, extra={"base_iteration": 0})
    repo.ingest_spilled(p, meta=ckpt.flat_row_meta(p))
    repo.fuse_pending(wait=True)
    np.testing.assert_allclose(np.asarray(repo.flat_base_host()), 4.0,
                               atol=1e-5)
