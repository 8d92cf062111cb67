"""Pallas TPU kernel: fused ColD Fusion repository update.

The Repository's fuse step is HBM-bandwidth-bound streaming arithmetic over
K contributor checkpoints.  A naive implementation reads each contribution
twice (once for the average, once for the §9 diff-norm screen) and the base
three times.  This kernel performs, in a single VMEM pass per block:

    fused = base + α·(Σ_k w_k θ_k − base)          (damped weighted average)
    sq_diff[k] += ||θ_k − base||²_block            (screening statistic)

so one streaming read of the staged contributions yields BOTH the fused
model and the §9 screening statistics — the Repository's single-pass
screen+fuse contract (see docs/fusion_engine.md).

Contract details:

* **zero-weight masking** — a contributor with weight exactly 0 contributes
  nothing to ``fused`` even if its parameters are non-finite (NaN·0 would
  otherwise poison the average).  This is what lets the Repository's second
  pass simply zero the weights of screened-out contributors and re-use the
  already-staged ``[K, N]`` buffer.  ``sq_diff`` is still computed from the
  raw values, so the screening statistic always reflects the real diff.
* **bf16 streaming, f32 accumulation** — contributions may arrive in bf16
  (half the HBM traffic); all arithmetic runs in f32 inside VMEM and the
  fused output is cast back to the base dtype.
* **donation** — ``donate=True`` donates the staged ``[K, N]`` buffer to
  XLA (the Repository discards it after the fuse), letting the backend
  reuse its pages for the output instead of allocating fresh ones.

TPU layout: the operands keep the layout XLA gives them — ``[K, N]``
contributions, ``[N]`` base/fused — so no relayout copy precedes or
follows the kernel.  A block is ``(K, BLOCK)`` / ``(BLOCK,)`` (BLOCK a
LANE multiple; the sublane dim equals the array's, which satisfies
Mosaic's tiling rule).  The grid is
``ceil(N / BLOCK)`` with no padding copy: the last block's columns past N
are dropped from the writes by Pallas and masked out of ``sq_diff`` by
the kernel.  The weighted sum is a VPU loop over the K rows with
the weights as SMEM scalars — no 1-D contraction, which Mosaic cannot
lower.  ``sq_diff`` accumulates as a ``[K, 1]`` output across the
sequential grid (same output block every step, an idiomatic Pallas
reduction).  The per-block working set is about (K+2)·BLOCK·4 B, well
inside the scoped VMEM at the default BLOCK.

**Per-shard use** (docs/sharding.md): the kernel is oblivious to whether
``[K, N]`` is the whole staging buffer or one block-cyclic shard of it —
the math is elementwise over N, so ``ops.fuse_flat_sharded`` simply runs
this launch on each shard's ``[K, shard_len]`` slice (tile-aligned by
construction: ``ShardedFlatSpec.block`` is a LANE multiple) and the
``sq_diff`` output becomes a *partial* that one ``psum`` completes.  The
weight normalization w/Σw is shard-invariant (weights are replicated), so
the fused output needs no communication at all.
"""
from __future__ import annotations

import functools
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.flat import LANE as _LANE  # min 1-D tile (8 sublanes x 128 lanes)

DEFAULT_BLOCK = 64 * 1024  # elems: (K+2)*256KB f32 at K=8 -> ~2.6 MB VMEM
_LANES = 128
_SUBLANES = _LANE // _LANES
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole (small) array
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _as_rows(x, n_pad: int):
    """Zero-pad ``[N]`` to ``n_pad`` and view it as ``[n_pad/128, 128]``."""
    if n_pad != x.shape[0]:
        x = jnp.concatenate([x, jnp.zeros((n_pad - x.shape[0],), x.dtype)])
    return x.reshape(n_pad // _LANES, _LANES)


def _make_fuse_kernel(k: int, n: int, block: int):
    ragged = n % block != 0

    def kernel(w_ref, wn_ref, alpha_ref, base_ref, contribs_ref, fused_ref,
               sq_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        base = base_ref[...].astype(jnp.float32)[None, :]  # [1, BLOCK]
        avg = jnp.zeros_like(base)
        for i in range(k):
            c = contribs_ref[i:i + 1, :].astype(jnp.float32)
            # zero-weight rows are masked out entirely: 0 * NaN must not
            # reach the sum
            avg = avg + jnp.where(w_ref[i] == 0.0, 0.0, c) * wn_ref[i]
        fused = base + alpha_ref[0] * (avg - base)
        fused_ref[...] = fused[0].astype(fused_ref.dtype)
        diff = contribs_ref[...].astype(jnp.float32) - base  # [K, BLOCK]
        sq = diff * diff
        if ragged:  # the last block reads past N: drop those columns
            col = (pl.program_id(0) * block
                   + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1))
            sq = jnp.where(col < n, sq, 0.0)
        sq_ref[...] += jnp.sum(sq, axis=1, keepdims=True)

    return kernel


def _cold_fuse_impl(base, contribs, weights, alpha, block, interpret):
    K, N = contribs.shape
    # a block is a whole number of LANE tiles, and no larger than N needs
    block = _round_up(min(block, max(N, 1)), _LANE)
    w = weights.astype(jnp.float32)
    alpha_arr = jnp.reshape(jnp.asarray(alpha, jnp.float32), (1,))
    row_spec = pl.BlockSpec((block,), lambda i: (i,))
    fused, sq = pl.pallas_call(
        _make_fuse_kernel(K, N, block),
        grid=(pl.cdiv(N, block),),
        in_specs=[_SMEM, _SMEM, _SMEM, row_spec,
                  pl.BlockSpec((K, block), lambda i: (0, i))],
        out_specs=[
            row_spec,
            pl.BlockSpec((K, 1), lambda i: (0, 0)),  # accumulated
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N,), base.dtype),
            jax.ShapeDtypeStruct((K, 1), jnp.float32),
        ],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(w, w / jnp.sum(w), alpha_arr, base, contribs)
    return fused, sq[:, 0]


_jit_fuse = functools.partial(jax.jit, static_argnames=("block", "interpret"))
_cold_fuse = _jit_fuse(_cold_fuse_impl)
_cold_fuse_donated = _jit_fuse(_cold_fuse_impl, donate_argnums=(1,))


def call_donated(fn, *args, **kw):
    """Invoke a donated-jit function; backends that decline the donation
    (CPU) emit a warning we deliberately swallow."""
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*donat.*")
        return fn(*args, **kw)


def cold_fuse(
    base: jax.Array,      # [N]
    contribs: jax.Array,  # [K, N], K >= 1
    weights: jax.Array,   # [K]
    alpha=1.0,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool = True,
    donate: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (fused [N], sq_diff [K]).  ``donate=True`` hands the
    ``contribs`` buffer to XLA for reuse — only pass buffers you will not
    touch again.  Oracle: ``repro.kernels.ref.cold_fuse``."""
    args = (base, contribs, weights, alpha)
    if donate:
        return call_donated(_cold_fuse_donated, *args, block=block,
                            interpret=interpret)
    return _cold_fuse(*args, block=block, interpret=interpret)


# ---------------------------------------------------------------------------
# decode_accum — weighted scatter-accumulate of compressed contribution deltas
# (docs/service_loop.md §Compressed submissions).  A compressed cohort
# arrives as [C, nb, kb] payload stacks (within-block int offsets +
# dequantized delta values); the fuse needs Σ_c w_c·Δ_c dense plus the per-
# contribution ||Δ_c||² screen statistic — and must get both WITHOUT ever
# materializing a dense [N] row per contributor.  The grid walks the nb
# codec blocks G at a time (block (C, G, kb): G is a sublane multiple and
# kb the whole last dim, so Mosaic's tiling rule holds).  TPU has no
# efficient scatter, so each codec block is decoded by a one-hot matmul on
# the MXU: an offset splits into (row, lane) = (offset // 128, offset %
# 128) of the block's [block/128, 128] tile view, and
#
#     out[r, l] = Σ_j (w·Δ)_j [row_j == r] · [lane_j == l]
#
# is a [block/128, kb] x [kb, 128] product of two small one-hot factors —
# duplicate offsets accumulate for free.  sq accumulates elementwise as a
# [C, G, kb] partial across the sequential grid.
# ---------------------------------------------------------------------------

DECODE_GROUP = 8  # codec blocks per grid step (a sublane multiple)


def _make_decode_kernel(C: int, G: int, rows: int):
    def kernel(w_ref, idx_ref, dv_ref, acc_ref, sq_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        kb = idx_ref.shape[-1]
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, kb), 0)
        lane_ids = jax.lax.broadcasted_iota(jnp.int32, (_LANES, kb), 0)
        out = [jnp.zeros((rows, _LANES), jnp.float32) for _ in range(G)]
        for c in range(C):
            idx = idx_ref[c]                          # [G, kb]
            dv = dv_ref[c].astype(jnp.float32)        # [G, kb]
            sq_ref[c] += dv * dv
            # zero-weight rows are masked out entirely: 0 * NaN must not
            # reach the sum
            wdv = jnp.where(w_ref[c] == 0.0, 0.0, dv) * w_ref[c]
            row, lane = idx // _LANES, idx % _LANES
            for g in range(G):
                a = jnp.where(row_ids == row[g:g + 1], wdv[g:g + 1], 0.0)
                b = (lane_ids == lane[g:g + 1]).astype(jnp.float32)
                out[g] = out[g] + jax.lax.dot_general(
                    a, b, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        acc_ref[...] = jnp.stack(out)

    return kernel


def _decode_accum_impl(indices, dvalues, weights, size, block, interpret):
    C, nb, kb = indices.shape
    G = min(DECODE_GROUP, nb)
    nb_pad = _round_up(nb, G)
    if nb_pad != nb:  # padding entries: offset 0, value 0 — they add nothing
        pad = ((0, 0), (0, nb_pad - nb), (0, 0))
        indices, dvalues = jnp.pad(indices, pad), jnp.pad(dvalues, pad)
    rows = block // _LANES
    payload_spec = pl.BlockSpec((C, G, kb), lambda i: (0, i, 0))
    acc, sq = pl.pallas_call(
        _make_decode_kernel(C, G, rows),
        grid=(nb_pad // G,),
        in_specs=[_SMEM, payload_spec, payload_spec],
        out_specs=[
            pl.BlockSpec((G, rows, _LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((C, G, kb), lambda i: (0, 0, 0)),  # accumulated
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb_pad, rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((C, G, kb), jnp.float32),
        ],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(weights.astype(jnp.float32), indices, dvalues)
    return acc.reshape(nb_pad * block)[:size], jnp.sum(sq, axis=(1, 2))


_decode_accum = _jit_fuse(
    _decode_accum_impl, static_argnames=("size", "block", "interpret"))


def decode_accum(
    indices: jax.Array,   # [C, nb, kb] int32 within-block offsets
    dvalues: jax.Array,   # [C, nb, kb] f32 dequantized deltas
    weights: jax.Array,   # [C]
    *,
    size: int,
    block: int,
    interpret: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (acc [size] = Σ_c w_c·Δ_c, sq [C] = ||Δ_c||²) — the fused
    decode+accumulate over a stacked compressed cohort.  ``block`` is the
    codec block (a LANE multiple); duplicate offsets accumulate.  Oracle:
    ``repro.kernels.ref.decode_accum``."""
    if indices.shape[0] == 0 or indices.shape[2] == 0:
        return (jnp.zeros((size,), jnp.float32),
                jnp.zeros((indices.shape[0],), jnp.float32))
    return _decode_accum(indices, dvalues, weights,
                         size=size, block=block, interpret=interpret)


# ---------------------------------------------------------------------------
# row_sketch — per-row block statistics for the novelty admission screen
# ---------------------------------------------------------------------------
#
# The service loop's content-based admission screen (docs/service_loop.md)
# needs, per submitted [N] row, a tiny fingerprint: bucketed tile sums
# (projections) and tile sq-norms — see kernels/ref.py:row_sketch for the
# exact contract.  Like cold_fuse this is HBM-bandwidth-bound streaming over
# the whole row, so the kernel reads each block exactly once and accumulates
# across the sequential grid (same output block every step).  A LANE tile
# is one (8, 128) slab of the [N/128, 128] row view, and tile t feeds
# bucket t % n_buckets.  The block is a whole number of n_buckets tiles, so
# every block starts at bucket 0 and the bucketing is a reshape to
# [tiles/n_buckets, n_buckets, 8, 128] summed over its leading axis — pure
# VPU adds into a [2, n_buckets, 8, 128] partial the wrapper reduces.


def _sketch_kernel(row_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    n_buckets = out_ref.shape[1]
    x = row_ref[...].astype(jnp.float32).reshape(
        -1, n_buckets, _SUBLANES, _LANES)
    out_ref[0] += jnp.sum(x, axis=0)
    out_ref[1] += jnp.sum(x * x, axis=0)


def _row_sketch_impl(row, n_buckets, block, interpret):
    (n,) = row.shape
    unit = n_buckets * _LANE  # one tile per bucket
    block = _round_up(min(block, _round_up(max(n, 1), _LANE)), unit)
    n_pad = _round_up(n, block)
    out = pl.pallas_call(
        _sketch_kernel,
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec((block // _LANES, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2, n_buckets, _SUBLANES, _LANES),
                               lambda i: (0, 0, 0, 0)),  # accumulated
        out_shape=jax.ShapeDtypeStruct((2, n_buckets, _SUBLANES, _LANES),
                                       jnp.float32),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(_as_rows(row, n_pad))
    return jnp.sum(out, axis=(2, 3))


_row_sketch = _jit_fuse(_row_sketch_impl,
                        static_argnames=("n_buckets", "block", "interpret"))


def row_sketch(
    row: jax.Array,  # [N]
    n_buckets: int = 32,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool = True,
) -> jax.Array:
    """Returns the ``[2, n_buckets]`` content sketch of one flat row in a
    single streaming read (tile-bucketed sums + sq sums; padding contributes
    0 to both).  ``block`` is rounded up to a whole number of
    ``n_buckets`` tiles.  Oracle: ``repro.kernels.ref.row_sketch``."""
    return _row_sketch(row, n_buckets=n_buckets, block=block, interpret=interpret)
