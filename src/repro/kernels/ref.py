"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.utils.flat import LANE as _LANE


# ---------------------------------------------------------------------------
# cold_fuse: K-way weighted parameter average + per-contribution diff norms
# ---------------------------------------------------------------------------


def cold_fuse(
    base: jax.Array,  # [N]
    contribs: jax.Array,  # [K, N]
    weights: jax.Array,  # [K] (need not be normalized)
    alpha: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (fused [N], sq_diff [K]).

    fused = base + alpha * (Σ_k w_k θ_k / Σ_k w_k − base)
    sq_diff[k] = ||θ_k − base||² (the §9 screening statistic).

    Zero-weight contributions are masked out of the average entirely (even
    non-finite ones — NaN·0 must not poison the sum), matching the Pallas
    kernel's single-pass screen+fuse contract; sq_diff always reflects the
    raw values.  The weighted sum is an f32 elementwise reduction, not a
    contraction, so no backend runs it at reduced matmul precision.
    """
    w = weights.astype(jnp.float32)
    cf = contribs.astype(jnp.float32)
    bf = base.astype(jnp.float32)
    masked = jnp.where((w == 0.0)[:, None], 0.0, cf)
    avg = jnp.sum((w / jnp.sum(w))[:, None] * masked, axis=0)
    fused = (bf + alpha * (avg - bf)).astype(base.dtype)
    sq = jnp.sum(jnp.square(cf - bf[None, :]), axis=1)
    return fused, sq


# ---------------------------------------------------------------------------
# decode_accum: weighted scatter-accumulate of compressed contribution deltas
# ---------------------------------------------------------------------------


def decode_accum(
    indices: jax.Array,   # [C, nb, kb] int — within-block offsets
    dvalues: jax.Array,   # [C, nb, kb] f32 — dequantized deltas (values·scales)
    weights: jax.Array,   # [C]
    *,
    size: int,
    block: int,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (acc [size], sq [C]) for C compressed contributions
    (``repro.utils.flat.DeltaPayload`` stacked along a leading axis):

        acc[n]  = Σ_c w_c · Δ_c[n]        (the fuse numerator's delta term)
        sq[c]   = Σ |Δ_c|²                (the §9 screening statistic)

    Entry j of block b lands at ``b·block + indices[c,b,j]``; duplicate
    offsets accumulate (scatter-add), padding-slot ``(0, 0)`` entries add
    zero, and anything past ``size`` is trimmed.  Zero-weight contributions
    are masked out of ``acc`` entirely (NaN·0 must not poison the sum —
    the same re-weighted-second-pass contract as ``cold_fuse``); ``sq``
    always reflects the raw decoded delta.
    """
    C, nb, kb = indices.shape
    w = weights.astype(jnp.float32)
    dv = dvalues.astype(jnp.float32)
    acc = jnp.zeros((nb * block,), jnp.float32)
    if C and kb:
        gi = (jnp.arange(nb, dtype=jnp.int32)[None, :, None] * block
              + indices.astype(jnp.int32))
        wdv = jnp.where((w == 0.0)[:, None, None], 0.0, dv) * w[:, None, None]
        acc = acc.at[gi.reshape(-1)].add(wdv.reshape(-1))
    sq = jnp.sum(dv * dv, axis=(1, 2))
    return acc[:size], sq


# ---------------------------------------------------------------------------
# row_sketch: per-row block statistics for the novelty admission screen
# ---------------------------------------------------------------------------


def _tile_stats(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[T*LANE] -> per-tile (sums [T], sq sums [T]) in one read."""
    tiles = x.reshape(-1, _LANE)
    return jnp.sum(tiles, axis=1), jnp.sum(tiles * tiles, axis=1)


def _bucketize(ts: jax.Array, tq: jax.Array, g: jax.Array,
               n_buckets: int) -> jax.Array:
    """Accumulate per-tile stats into their buckets (tile with global index
    ``g`` lands in bucket ``g % n_buckets``).  Dense one-hot matmul instead
    of a scatter: ``n_buckets`` is small and the contraction lowers on
    every backend."""
    onehot = (g[:, None] % n_buckets
              == jnp.arange(n_buckets)[None, :]).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST  # f32 sums, not one bf16 MXU pass
    return jnp.stack([jnp.matmul(ts, onehot, precision=hi),
                      jnp.matmul(tq, onehot, precision=hi)])


def row_sketch(row: jax.Array, n_buckets: int = 32) -> jax.Array:
    """Content sketch of one flat ``[N]`` row in a single read.

    The row is cut into LANE-element tiles; tile ``t`` feeds bucket
    ``t % n_buckets`` of two statistics:

        sketch[0, j] = Σ_{tiles t ≡ j} Σ_i row[t·LANE + i]      (projection)
        sketch[1, j] = Σ_{tiles t ≡ j} Σ_i row[t·LANE + i]²     (sq norm)

    Returns ``[2, n_buckets]`` float32.  Both statistics give lower bounds
    on the distance between two rows (Cauchy–Schwarz over the projections,
    the reverse triangle inequality over the blockwise norms), which is
    what ``repro.utils.flat.CohortSketch`` screens with.  Zero padding
    contributes nothing, so the sketch is invariant to the block-cyclic
    layout: ``row_sketch_shard`` partials psum to exactly this value.
    """
    x = jnp.asarray(row).astype(jnp.float32)
    pad = (-x.shape[-1]) % _LANE
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
    ts, tq = _tile_stats(x)
    return _bucketize(ts, tq, jnp.arange(ts.shape[0]), n_buckets)


def row_sketch_shard(slab: jax.Array, shard_index, n_shards: int,
                     block: int, n_buckets: int = 32) -> jax.Array:
    """One shard's sketch *partial* from its block-cyclic ``[shard_len]``
    slice (``ShardedFlatSpec``: layout block ``j`` lives on shard
    ``j % n_shards`` at slot ``j // n_shards``).

    The slice's tile at (slot ``u``, within-block tile ``v``) is global
    tile ``(u·n_shards + shard_index)·(block/LANE) + v``, so bucket
    membership matches the portable row and summing (psum-ing) the S
    partials reproduces ``row_sketch`` of the full ``[N]`` row exactly.
    ``shard_index`` may be traced (``jax.lax.axis_index`` under shard_map).
    """
    x = jnp.asarray(slab).astype(jnp.float32)
    tpb = block // _LANE
    ts, tq = _tile_stats(x)
    t = jnp.arange(ts.shape[0])
    g = ((t // tpb) * n_shards + shard_index) * tpb + t % tpb
    return _bucketize(ts, tq, g, n_buckets)


# ---------------------------------------------------------------------------
# flash attention (causal, optional sliding window, GQA)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,  # [B, Sq, Hq, hd]
    k: jax.Array,  # [B, Sk, Hkv, hd]
    v: jax.Array,  # [B, Sk, Hkv, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
) -> jax.Array:
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    qf = q.astype(jnp.float32) * (hd ** -0.5)
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    q_pos = q_offset + jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# rwkv6 recurrence (data-dependent decay)
# ---------------------------------------------------------------------------


def rwkv6_scan(
    r: jax.Array,  # [B, T, H, hd]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # [B, T, H, hd] per-step decay in (0, 1]
    u: jax.Array,  # [H, hd] current-token bonus
    s0: Optional[jax.Array] = None,  # [B, H, hd, hd] f32
) -> Tuple[jax.Array, jax.Array]:
    """Sequential oracle.  Returns (y [B, T, H, hd], s_final [B, H, hd, hd]).

        y_t = r_t · (u ⊙ k_t v_tᵀ + S_t);  S_{t+1} = w_t ⊙ S_t + k_t v_tᵀ
    """
    B, T, H, hd = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, hd, hd), jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None].astype(jnp.float32) * v_t[..., None, :].astype(jnp.float32)
        y = jnp.einsum("bhi,bhij->bhj", r_t.astype(jnp.float32), u[None, :, :, None] * kv + S)
        S = w_t[..., :, None].astype(jnp.float32) * S + kv
        return S, y

    inputs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    sT, ys = jax.lax.scan(step, s0, inputs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), sT
