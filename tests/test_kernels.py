"""Pallas kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cold_fuse import cold_fuse
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_scan import rwkv6_scan

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 4e-2


# ---------------------------------------------------------------------------
# cold_fuse
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K,N", [(2, 128), (4, 1000), (8, 70_000), (16, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alpha", [1.0, 0.3])
def test_cold_fuse_sweep(K, N, dtype, alpha):
    ks = jax.random.split(KEY, 3)
    base = jax.random.normal(ks[0], (N,), jnp.float32).astype(dtype)
    contribs = jax.random.normal(ks[1], (K, N), jnp.float32).astype(dtype)
    w = jax.random.uniform(ks[2], (K,)) + 0.05
    f_ref, sq_ref = ref.cold_fuse(base, contribs, w, alpha)
    f_k, sq_k = cold_fuse(base, contribs, w, alpha, block=4096)
    np.testing.assert_allclose(
        np.asarray(f_k, np.float32), np.asarray(f_ref, np.float32), atol=_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(sq_k), np.asarray(sq_ref), rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_compressed_combine_matches_decoded_rows(dtype):
    """The compressed fuse's finish from the decoded accumulator equals the
    plain fuse over the decoded rows ``base + Δ_c`` (zero-weight dense rows
    still masked, even non-finite ones)."""
    ks = jax.random.split(KEY, 4)
    K, C, N = 3, 2, 5000
    base = jax.random.normal(ks[0], (N,), jnp.float32).astype(dtype)
    dense = jax.random.normal(ks[1], (K, N), jnp.float32).astype(dtype)
    dense = dense.at[0].set(jnp.nan)
    deltas = jax.random.normal(ks[2], (C, N), jnp.float32) * 0.1
    wd = jnp.asarray([0.0, 1.5, 0.5], jnp.float32)
    wc = jnp.asarray([1.0, 2.0], jnp.float32)
    acc = jnp.sum(wc[:, None] * deltas, axis=0)
    sq_comp = jnp.sum(deltas * deltas, axis=1)
    fused, sq = ops._compressed_combine(base, acc, wc, sq_comp, dense, wd,
                                        jnp.float32(0.7))
    rows = jnp.concatenate(
        [dense.astype(jnp.float32), base.astype(jnp.float32) + deltas])
    f_rows, sq_rows = ref.cold_fuse(base.astype(jnp.float32), rows,
                                    jnp.concatenate([wd, wc]), 0.7)
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(f_rows), atol=_tol(dtype))
    np.testing.assert_allclose(np.asarray(sq)[1:], np.asarray(sq_rows)[1:],
                               rtol=1e-4)
    assert np.isnan(np.asarray(sq)[0])


def test_cold_fuse_uniform_weights_is_mean():
    base = jnp.zeros((256,))
    contribs = jnp.stack([jnp.full((256,), float(i)) for i in range(4)])
    fused, sq = cold_fuse(base, contribs, jnp.ones((4,)), 1.0, block=256)
    np.testing.assert_allclose(np.asarray(fused), 1.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sq), [0.0, 256.0, 1024.0, 2304.0], rtol=1e-5)


def test_fuse_pytrees_matches_fusion_average(tiny_cfg, key):
    from repro.core import fusion
    from repro.models import encoder as E

    bodies = [E.init_encoder_body(tiny_cfg, jax.random.PRNGKey(i)) for i in range(3)]
    want = fusion.average(bodies)
    got, sq = ops.fuse_pytrees(bodies[0], bodies)
    flat_w = jax.tree.leaves(want)
    flat_g = jax.tree.leaves(got)
    for a, b in zip(flat_w, flat_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    assert float(sq[0]) == 0.0 and float(sq[1]) > 0.0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,Hkv,hd,causal,window,bq,bk",
    [
        (2, 128, 128, 4, 2, 32, True, None, 64, 64),
        (1, 256, 256, 4, 1, 64, True, 96, 64, 64),
        (2, 64, 64, 2, 2, 32, False, None, 32, 32),
        (1, 64, 64, 8, 8, 16, True, 16, 32, 32),
        (1, 128, 128, 2, 1, 128, True, None, 128, 128),
    ],
)
def test_flash_attention_sweep(B, Sq, Sk, Hq, Hkv, hd, causal, window, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, hd), jnp.float32)
    o_ref = ref.flash_attention(q, k, v, causal=causal, window=window)
    o_k = flash_attention(q, k, v, causal=causal, window=window, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.float32).astype(dtype)
    o_ref = ref.flash_attention(q, k, v, causal=True)
    o_k = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_ref, np.float32), atol=4e-2
    )


def test_flash_attention_decode_offset():
    """One-token decode against a longer cache (the serve_step pattern)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    for off in (0, 63, 127):
        o_ref = ref.flash_attention(q, k, v, causal=True, q_offset=off)
        o_k = flash_attention(q, k, v, causal=True, q_offset=off, block_q=1, block_k=64)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref), atol=2e-5)


def test_chunked_sdpa_matches_dense():
    """The XLA-flash fallback (used by dry-runs) equals the dense path."""
    from repro.models.layers import _sdpa, _sdpa_chunked

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 1024, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 1024, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 1024, 2, 32), jnp.float32)
    for window in (None, 256):
        dense = _sdpa(q, k, v, causal=True, window=window, q_offset=0)
        chunked = _sdpa_chunked(q, k, v, causal=True, window=window, q_offset=0, chunk=256)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense), atol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,T,H,hd,chunk",
    [(2, 32, 2, 16, 16), (1, 64, 3, 32, 16), (2, 48, 1, 64, 16), (1, 16, 4, 8, 8)],
)
def test_rwkv6_sweep(B, T, H, hd, chunk):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, hd), jnp.float32)
    logw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd)) - 1.5), -4.0, -1e-3)
    u = jax.random.normal(ks[4], (H, hd), jnp.float32) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, hd, hd), jnp.float32) * 0.3
    y_ref, sT_ref = ref.rwkv6_scan(r, k, v, jnp.exp(logw), u, s0)
    y_k, sT_k = rwkv6_scan(r, k, v, logw, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=5e-4)
    np.testing.assert_allclose(np.asarray(sT_k), np.asarray(sT_ref), atol=5e-4)


def test_rwkv6_state_chaining():
    """Running two half-sequences with state carry == one full sequence."""
    ks = jax.random.split(KEY, 5)
    B, T, H, hd = 1, 32, 2, 16
    r = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, hd), jnp.float32)
    logw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd)) - 1.5), -4.0, -1e-3)
    u = jax.random.normal(ks[4], (H, hd), jnp.float32) * 0.5
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y_full, sT_full = rwkv6_scan(r, k, v, logw, u, s0, chunk=16)
    y1, s1 = rwkv6_scan(r[:, :16], k[:, :16], v[:, :16], logw[:, :16], u, s0, chunk=16)
    y2, s2 = rwkv6_scan(r[:, 16:], k[:, 16:], v[:, 16:], logw[:, 16:], u, s1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=5e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sT_full), atol=5e-4)


def test_ops_rwkv_clamp_contract():
    """ops.rwkv6_mix clamps log-decay into the kernel contract."""
    ks = jax.random.split(KEY, 5)
    B, T, H, hd = 1, 16, 1, 8
    args = [jax.random.normal(ks[i], (B, T, H, hd), jnp.float32) for i in range(3)]
    logw = jnp.full((B, T, H, hd), -50.0)  # way below the floor
    u = jnp.zeros((H, hd))
    s0 = jnp.ones((B, H, hd, hd), jnp.float32)
    y, sT = ops.rwkv6_mix(*args, logw, u, s0)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(sT).all())
