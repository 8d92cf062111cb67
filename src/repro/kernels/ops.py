"""jit'd public wrappers around the Pallas kernels.

On TPU the kernels compile to Mosaic; on CPU (this container) they run in
``interpret=True`` mode for correctness.  ``use_kernels(False)`` (or the
REPRO_NO_KERNELS env var) routes everything to the pure-jnp oracles — the
dry-run lowering path uses the oracles because Pallas does not lower to the
CPU host platform.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ref
from repro.kernels.cold_fuse import call_donated as _call_donated
from repro.kernels.cold_fuse import cold_fuse as _cold_fuse_kernel
from repro.kernels.cold_fuse import decode_accum as _decode_accum_kernel
from repro.kernels.cold_fuse import row_sketch as _row_sketch_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv_kernel
from repro.launch.sharding import axes_entry, axes_extent, norm_axes
from repro.utils.flat import SKETCH_BUCKETS, FlatSpec, StagedBuffer

RWKV_LOGW_FLOOR = -4.0  # kernel contract (see rwkv6_scan docstring)

_STATE = {"enabled": os.environ.get("REPRO_NO_KERNELS", "0") != "1"}


def use_kernels(enabled: bool) -> None:
    _STATE["enabled"] = bool(enabled)


def kernels_enabled() -> bool:
    return _STATE["enabled"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------


def _staged(contribs):
    """Fuse operands accept either a raw array or an explicit
    ``StagedBuffer`` handle (the async double-buffered Repository hands the
    back buffer around as a handle — docs/async_repository.md)."""
    return contribs.data if isinstance(contribs, StagedBuffer) else contribs


def fuse_flat(base, contribs, weights, alpha: float = 1.0,
              *, donate: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Fused repository update over flattened parameter vectors.
    Returns (fused [N], sq_diff [K]).  ``contribs`` is the staged ``[K, N]``
    operand — a raw array or a ``StagedBuffer`` handle.  ``donate=True``
    hands the staged buffer to the backend for reuse (kernel path only).

    Unlike attention/rwkv, the Mosaic kernel only runs on real TPUs: the
    interpret-mode emulation is a correctness harness, several times slower
    than plain XLA, so on other backends the (jitted) flat jnp oracle serves
    the same single-pass contract (one read of the staged [K, N] buffer
    yields both the fused model and the screening statistics)."""
    contribs = _staged(contribs)
    if kernels_enabled() and not _interpret():
        return _cold_fuse_kernel(
            base, contribs, weights, alpha, interpret=False, donate=donate)
    if donate:
        return _call_donated(_ref_fuse_donated, base, contribs, weights, alpha)
    return _ref_fuse(base, contribs, weights, alpha)


_ref_fuse = jax.jit(ref.cold_fuse)
_ref_fuse_donated = jax.jit(ref.cold_fuse, donate_argnums=(1,))


def fuse_pytrees(base_tree, contrib_trees, weights=None, alpha: float = 1.0,
                 *, spec: Optional[FlatSpec] = None, donate: bool = False):
    """Repository fuse over pytrees: flatten the WHOLE model into one
    contiguous buffer per contributor, stack to [K, N], and issue ONE
    streaming kernel launch (not one padded launch per leaf).

    Returns (fused_tree, sq_diff [K] over all parameters).  Pass ``spec``
    when the caller already holds the FlatSpec (saves re-deriving it)."""
    K = len(contrib_trees)
    w = jnp.ones((K,), jnp.float32) if weights is None else jnp.asarray(weights, jnp.float32)
    if spec is None:
        spec = FlatSpec.from_tree(base_tree)
    base_flat = spec.flatten(base_tree)
    stage = jnp.stack([spec.flatten(t) for t in contrib_trees])
    fused, sq = fuse_flat(base_flat, stage, w, alpha, donate=donate)
    return spec.unflatten(fused), sq


# ---------------------------------------------------------------------------
# sharded flat fuse (docs/sharding.md) — the SAME single-pass screen+fuse
# contract as fuse_flat, run per block-cyclic shard under shard_map.  The
# fused output is elementwise over N (zero communication); the per-shard
# sq_diff partials are completed by exactly ONE psum per fuse.  The
# single-device fuse_flat / the per-leaf engine remain the oracles.
# ---------------------------------------------------------------------------

Axes = Union[str, Sequence[str]]


def _shard_cold_fuse(base, contribs, weights, alpha, *, use_kernel: bool):
    """The per-shard screen+fuse: the single-device cold_fuse contract run on
    one ``[K, shard_len]`` slice.  Returns (fused [shard_len], sq PARTIAL [K]).

    The weight normalization w/Σw uses the replicated global weights, so it
    is identical on every shard; zero-weight masking (the re-weighted second
    pass of the screen) therefore behaves exactly as on a single device."""
    if use_kernel:
        return _cold_fuse_kernel(base, contribs, weights, alpha, interpret=False)
    return ref.cold_fuse(base, contribs, weights, alpha)


@functools.lru_cache(maxsize=32)
def _sharded_fuse_fn(mesh: Mesh, axes: Tuple[str, ...], use_kernel: bool):
    """Build (once per mesh/axes) the jitted shard_map fuse over a
    ``[S, L]`` base and ``[K, S, L]`` staging buffer laid out by
    ``ShardedFlatSpec``.  Exactly one collective: the sq_diff psum."""
    row_spec = P(axes_entry(axes), None)
    stage_spec = P(None, axes_entry(axes), None)

    def local(base, contribs, weights, alpha):
        # local blocks carry a size-1 stub of the shard dim: strip/re-add it
        fused, sq = _shard_cold_fuse(
            base[0], contribs[:, 0, :], weights, alpha[0], use_kernel=use_kernel)
        return fused[None], jax.lax.psum(sq, axes)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(row_spec, stage_spec, P(), P()),
        out_specs=(row_spec, P()),
        check_vma=False,
    )
    return jax.jit(fn)


def fuse_flat_sharded(
    base: jax.Array,      # [S, shard_len] — sharded over `axes`
    contribs: jax.Array,  # [K, S, shard_len]
    weights: jax.Array,   # [K] (replicated)
    alpha=1.0,
    *,
    mesh: Mesh,
    axes: Axes,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed fuse_flat over a block-cyclic staging layout.

    Returns (fused [S, shard_len] sharded like ``base``, sq_diff [K]
    replicated).  ``contribs`` is the staged operand — a raw array or a
    ``StagedBuffer`` handle.  Padding introduced by the layout is zero in
    both base and contributions, so it cancels in the diff and never biases
    ``sq_diff``.
    """
    contribs = _staged(contribs)
    ax = norm_axes(axes)
    use_kernel = kernels_enabled() and not _interpret()
    fn = _sharded_fuse_fn(mesh, ax, use_kernel)
    return fn(base, contribs,
              jnp.asarray(weights, jnp.float32),
              jnp.asarray(jnp.reshape(alpha, (1,)), jnp.float32))


@functools.lru_cache(maxsize=32)
def _cohort_fuse_fn(mesh: Mesh, contrib_axes: Tuple[str, ...],
                    shard_axes: Tuple[str, ...], alpha: float):
    """Mesh-level cohort fuse over a ``[C, S, L]`` stage: every contributor
    slab relaxes toward the α-damped cohort mean.

    Same sharded-flat structure as ``_sharded_fuse_fn`` with the roles of
    the axes swapped: here the *contributor* dim is the sharded reduction
    dim, so the per-shard partial is the local weighted sum over C_local and
    the single psum (over the contributor axes) completes the mean, so no
    GSPMD ``concat -> mean`` over a sharded axis ever lowers and the stage
    needs no sharding pin (see docs/sharding.md)."""
    in_spec = P(axes_entry(contrib_axes),
                axes_entry(shard_axes) if shard_axes else None, None)
    c_axes = axes_extent(mesh, contrib_axes)

    def local(x):  # [C_local, S_local(=1 when sharded), L]
        xf = x.astype(jnp.float32)
        # total cohort size: local slabs x contributor-axis extent
        part = jnp.sum(xf, axis=0, keepdims=True) / (x.shape[0] * c_axes)
        mean = jax.lax.psum(part, contrib_axes)
        if alpha != 1.0:
            fused = xf * (1.0 - alpha) + mean * alpha
        else:
            fused = jnp.broadcast_to(mean, xf.shape)
        return fused.astype(x.dtype)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_spec,),
                       out_specs=in_spec, check_vma=False)
    return jax.jit(fn)


def cohort_fuse_sharded(
    stage: jax.Array,  # [C, S, shard_len] — C over contrib_axes, S over shard_axes
    *,
    mesh: Mesh,
    contrib_axes: Axes,
    shard_axes: Axes = (),
    alpha: float = 1.0,
) -> jax.Array:
    """θ_c ← θ_c + α·(mean_c θ_c − θ_c), one psum over the contributor axes.

    The mesh-level counterpart of ``fuse_flat_sharded`` (the Repository
    path): both lay the flat buffer out block-cyclically and complete a
    per-shard partial with a single all-reduce; they differ only in which
    dim the psum runs over (sq_diff over the shard axes there, the
    contributor mean here).  ``stage`` accepts a raw array or a
    ``StagedBuffer`` handle."""
    stage = _staged(stage)
    fn = _cohort_fuse_fn(
        mesh, norm_axes(contrib_axes), norm_axes(shard_axes), float(alpha))
    return fn(stage)


# ---------------------------------------------------------------------------
# compressed fuse — screen+fuse directly over delta-compressed contributions
# (docs/service_loop.md §Compressed submissions).  A compressed contribution
# is θ_c = base + Δ_c with Δ_c carried as a DeltaPayload; substituting into
# the fuse gives
#
#     fused = base + α·[(Σ_d w_d θ_d + (Σ_c w_c)·base + Σ_c w_c Δ_c)/Σw − base]
#
# so the ONLY dense quantity the compressed side needs is the single
# accumulator Σ_c w_c Δ_c — one dense [N] total, never one per contributor —
# and the §9 screen statistic is ||Δ_c||² straight from the sparse payload.
# decode_accum produces both in one pass (Pallas on TPU, jnp oracle
# elsewhere); the sharded variant keeps the one-psum-per-fuse contract.
# ---------------------------------------------------------------------------


def decode_accum(indices, values, scales, weights, *,
                 size: int, block: int) -> Tuple[jax.Array, jax.Array]:
    """Decode+accumulate a stacked compressed cohort: returns
    (acc [size] = Σ_c w_c·Δ_c, sq [C] = ||Δ_c||²).  ``indices``/``values``
    are the stacked ``[C, nb, kb]`` payload arrays (any int/numeric dtype —
    cast internally), ``scales`` is ``[C, nb]``, ``block`` the codec block.
    Zero-weight contributions are masked out of ``acc``; ``sq`` always
    reflects the raw decoded delta."""
    idx = jnp.asarray(indices, jnp.int32)
    dv = (jnp.asarray(values, jnp.float32)
          * jnp.asarray(scales, jnp.float32)[..., None])
    w = jnp.asarray(weights, jnp.float32)
    if idx.shape[0] == 0 or idx.shape[2] == 0:
        return jnp.zeros((size,), jnp.float32), jnp.zeros((idx.shape[0],), jnp.float32)
    if kernels_enabled() and not _interpret():
        return _decode_accum_kernel(idx, dv, w, size=size, block=block,
                                    interpret=False)
    return _ref_decode(idx, dv, w, size=size, block=block)


_ref_decode = jax.jit(ref.decode_accum, static_argnames=("size", "block"))


def _combine_math(base, acc, comp_weights, sq_comp, dense, dense_weights,
                  alpha):
    """Finish the compressed fuse from the decoded accumulator: combined
    normalization over dense + compressed weights, zero-weight masking on
    the dense side, sq ordered (dense..., compressed...).  The weighted sum
    is an f32 elementwise reduction, not a contraction, so no backend runs
    it at reduced matmul precision."""
    bf = base.astype(jnp.float32)
    wd = dense_weights.astype(jnp.float32)
    wc = comp_weights.astype(jnp.float32)
    w_tot = jnp.sum(wd) + jnp.sum(wc)
    df = dense.astype(jnp.float32)
    masked = jnp.where((wd == 0.0)[:, None], 0.0, df)
    num = jnp.sum(wd[:, None] * masked, axis=0) + jnp.sum(wc) * bf + acc
    fused = (bf + alpha * (num / w_tot - bf)).astype(base.dtype)
    sq_dense = jnp.sum(jnp.square(df - bf[None, :]), axis=1)
    return fused, jnp.concatenate([sq_dense, sq_comp])


_compressed_combine = jax.jit(_combine_math)


def fuse_flat_compressed(
    base: jax.Array,       # [N]
    indices, values, scales,  # stacked payloads: [C, nb, kb] / [C, nb]
    comp_weights,          # [C]
    alpha=1.0,
    *,
    block: int,
    dense=None,            # optional dense [K, N] side of a mixed cohort
    dense_weights=None,    # [K]
) -> Tuple[jax.Array, jax.Array]:
    """Fused repository update consuming delta-compressed contributions
    directly.  Returns (fused [N], sq_diff [K+C]) with sq ordered
    (dense contributions first, compressed after) — the same single-pass
    screen+fuse contract as ``fuse_flat``, but no dense ``[N]`` row is ever
    materialized per compressed contributor.  Oracle identity: with exact
    payloads this equals ``fuse_flat(base, stack(dense + decoded), w)``."""
    N = int(base.shape[0])
    acc, sq_comp = decode_accum(indices, values, scales, comp_weights,
                                size=N, block=block)
    if dense is None:
        dense = jnp.zeros((0, N), base.dtype)
        dense_weights = jnp.zeros((0,), jnp.float32)
    return _compressed_combine(
        base, acc, jnp.asarray(comp_weights, jnp.float32), sq_comp,
        _staged(dense), jnp.asarray(dense_weights, jnp.float32),
        jnp.asarray(alpha, jnp.float32))


@functools.lru_cache(maxsize=32)
def _compressed_sharded_fn(mesh: Mesh, axes: Tuple[str, ...], block: int,
                           use_kernel: bool, has_dense: bool):
    """Build (once per mesh/layout) the jitted shard_map compressed fuse
    over per-shard payload stacks ``[C, S, nb, kb]``.  Exactly one
    collective: the psum completing the concatenated (dense..., compressed...)
    sq partials — the fused output needs no communication at all."""
    row_spec = P(axes_entry(axes), None)
    stage_spec = P(None, axes_entry(axes), None)
    comp_spec = P(None, axes_entry(axes), None, None)
    scl_spec = P(None, axes_entry(axes), None)

    def _local_decode(idx, val, scl, wc, length):
        dv = val.astype(jnp.float32) * scl.astype(jnp.float32)[..., None]
        if idx.shape[0] == 0 or idx.shape[2] == 0:
            return (jnp.zeros((length,), jnp.float32),
                    jnp.zeros((idx.shape[0],), jnp.float32))
        if use_kernel:
            return _decode_accum_kernel(idx.astype(jnp.int32), dv, wc,
                                        size=length, block=block,
                                        interpret=False)
        return ref.decode_accum(idx.astype(jnp.int32), dv, wc,
                                size=length, block=block)

    if has_dense:
        def local(base, idx, val, scl, wc, dense, wd, alpha):
            # local blocks carry a size-1 stub of the shard dim: strip it
            acc, sq_comp = _local_decode(
                idx[:, 0], val[:, 0], scl[:, 0], wc, base.shape[1])
            fused, sq = _combine_math(base[0], acc, wc, sq_comp,
                                      dense[:, 0, :], wd, alpha[0])
            return fused[None], jax.lax.psum(sq, axes)

        in_specs = (row_spec, comp_spec, comp_spec, scl_spec, P(),
                    stage_spec, P(), P())
    else:
        def local(base, idx, val, scl, wc, alpha):
            acc, sq_comp = _local_decode(
                idx[:, 0], val[:, 0], scl[:, 0], wc, base.shape[1])
            dense = jnp.zeros((0, base.shape[1]), base.dtype)
            wd = jnp.zeros((0,), jnp.float32)
            fused, sq = _combine_math(base[0], acc, wc, sq_comp,
                                      dense, wd, alpha[0])
            return fused[None], jax.lax.psum(sq, axes)

        in_specs = (row_spec, comp_spec, comp_spec, scl_spec, P(), P())

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=(row_spec, P()),
        check_vma=False,
    )
    return jax.jit(fn)


def fuse_flat_compressed_sharded(
    base: jax.Array,       # [S, shard_len] — sharded over `axes`
    indices, values, scales,  # [C, S, nb, kb] / [C, S, nb] per-shard stacks
    comp_weights,          # [C] (replicated)
    alpha=1.0,
    *,
    mesh: Mesh,
    axes: Axes,
    block: int,
    dense=None,            # optional dense [K, S, shard_len] side
    dense_weights=None,    # [K]
) -> Tuple[jax.Array, jax.Array]:
    """Distributed ``fuse_flat_compressed`` over a block-cyclic layout:
    each shard decodes its own payload slices (``delta_encode_sharded``
    order) and fuses locally; the concatenated sq partials are completed by
    exactly ONE psum — the same one-all-reduce contract as
    ``fuse_flat_sharded`` (docs/sharding.md).  Returns (fused [S, shard_len]
    sharded like ``base``, sq_diff [K+C] replicated, dense first)."""
    ax = norm_axes(axes)
    use_kernel = kernels_enabled() and not _interpret()
    wc = jnp.asarray(comp_weights, jnp.float32)
    alpha_arr = jnp.asarray(jnp.reshape(alpha, (1,)), jnp.float32)
    idx = jnp.asarray(indices)
    val = jnp.asarray(values)
    scl = jnp.asarray(scales)
    if dense is None:
        fn = _compressed_sharded_fn(mesh, ax, int(block), use_kernel, False)
        return fn(base, idx, val, scl, wc, alpha_arr)
    fn = _compressed_sharded_fn(mesh, ax, int(block), use_kernel, True)
    return fn(base, idx, val, scl, wc, _staged(dense),
              jnp.asarray(dense_weights, jnp.float32), alpha_arr)


# ---------------------------------------------------------------------------
# row_sketch — the novelty admission screen's per-row fingerprint
# (docs/service_loop.md).  Single-device: one streaming read of the [N] row
# (Pallas kernel on TPU, jitted jnp oracle elsewhere).  Sharded: per-shard
# partials under shard_map completed by exactly ONE psum — the same
# one-all-reduce comm contract as the sharded fuse (docs/sharding.md).
# ---------------------------------------------------------------------------


def row_sketch(row: jax.Array, n_buckets: int = SKETCH_BUCKETS) -> jax.Array:
    """Content sketch of one flat ``[N]`` row: ``[2, n_buckets]`` f32 of
    tile-bucketed sums and sq sums, in a single read of the row.  The host
    logic that screens with it lives in ``repro.utils.flat.CohortSketch``."""
    if kernels_enabled() and not _interpret():
        return _row_sketch_kernel(row, n_buckets, interpret=False)
    return _ref_sketch(row, n_buckets)


_ref_sketch = jax.jit(ref.row_sketch, static_argnums=(1,))


@functools.lru_cache(maxsize=32)
def _sharded_sketch_fn(mesh: Mesh, axes: Tuple[str, ...], n_shards: int,
                       block: int, n_buckets: int):
    """Build (once per mesh/layout) the jitted shard_map sketch over a
    block-cyclic ``[S, shard_len]`` row.  Exactly one collective: the psum
    completing the per-shard partials."""
    row_spec = P(axes_entry(axes), None)

    def local(row):  # [1, shard_len] local stub of the shard dim
        idx = jnp.int32(0)
        for a in axes:  # linear shard index, first axis most significant
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        part = ref.row_sketch_shard(row[0], idx, n_shards, block, n_buckets)
        return jax.lax.psum(part, axes)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(row_spec,), out_specs=P(),
                       check_vma=False)
    return jax.jit(fn)


def row_sketch_sharded(
    row: jax.Array,  # [S, shard_len] — sharded over `axes`
    *,
    mesh: Mesh,
    axes: Axes,
    block: int,
    n_buckets: int = SKETCH_BUCKETS,
) -> jax.Array:
    """Distributed ``row_sketch`` over a ``ShardedFlatSpec`` placement:
    each shard sketches its own slice (bucket ids derived from the
    block-cyclic layout, so membership matches the portable row) and one
    ``psum`` completes the ``[2, n_buckets]`` result, replicated.  ``block``
    is the layout's ``ShardedFlatSpec.block``."""
    ax = norm_axes(axes)
    fn = _sharded_sketch_fn(mesh, ax, int(row.shape[0]), int(block), n_buckets)
    return fn(row)


def attention(q, k, v, *, causal=True, window: Optional[int] = None, q_offset: int = 0,
              block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Blocked attention (GQA, causal, sliding window)."""
    if kernels_enabled():
        return _flash_kernel(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=block_q, block_k=block_k, interpret=_interpret(),
        )
    return ref.flash_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)


def rwkv6_mix(r, k, v, logw, u, s0, *, chunk: int = 16) -> Tuple[jax.Array, jax.Array]:
    """Chunked RWKV6 recurrence.  ``logw`` is clamped to the kernel contract
    (a per-step decay below e^-4 zeroes state within two tokens anyway)."""
    logw = jnp.clip(logw, RWKV_LOGW_FLOOR, 0.0)
    if kernels_enabled():
        return _rwkv_kernel(r, k, v, logw, u, s0, chunk=chunk, interpret=_interpret())
    return ref.rwkv6_scan(r, k, v, jnp.exp(logw), u, s0)
