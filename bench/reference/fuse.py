"""Plain reference of a ColD Fusion round (paper section 3, with the
section 9 screen) over flat contribution rows.

fused = base + alpha * (sum_k w_k row_k / sum_k w_k - base), rounded to the
row dtype; sq[k] = ||row_k - base||^2; the screen rejects non-finite and
zero distances and, in a cohort of three or more, any norm above
median + t * max(MAD, 0.05 * median).  Arithmetic in float32;
``prec="fp8"`` first rounds every row to float8 e4m3 (per-row scale): the
control.

``fuse`` takes the cohort as one ``[K, N]`` array.  ``fuse_tree``
computes the same round from the K pytrees as they are held, on the
device or in host memory, leaf by leaf and in blocks along each leaf's
leading axis (at most ``BLOCK`` elements, 64 MB in float32), so that a
cohort larger than a chip's memory as one array is never stacked, copied
or widened whole.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .common import FP8_MAX, _round_e4m3, quantize

# elements of one block of a leaf
BLOCK = 1 << 24


@functools.partial(jax.jit, static_argnames=("prec",))
def fuse(base, rows, weights, alpha, *, prec: str = "f32"):
    """base [N], rows [K, N] (both in the row dtype), weights [K] ->
    (fused [N] in the row dtype, sq [K] float32)."""
    b = base.astype(jnp.float32)
    r = jax.vmap(lambda x: quantize(x, prec))(rows)
    w = weights.astype(jnp.float32)
    avg = jnp.tensordot(w / jnp.sum(w), r, axes=1,
                        precision=jax.lax.Precision.HIGHEST)
    fused = (b + alpha * (avg - b)).astype(base.dtype)
    sq = jnp.sum(jnp.square(r - b[None]), axis=1)
    return fused, sq


def screen(norms: Sequence[float], mad_threshold: float) -> List[int]:
    """Indices the section 9 screen accepts."""
    x = np.asarray(norms, np.float64)
    ok = np.isfinite(x)
    fin = x[ok]
    med = float(np.median(fin)) if fin.size else 0.0
    mad = float(np.median(np.abs(fin - med))) if fin.size else 0.0
    cut = med + mad_threshold * max(mad, 1e-12 + 0.05 * med)
    out = []
    for i, n in enumerate(x):
        if not ok[i] or n == 0.0 or (fin.size >= 3 and n > cut):
            continue
        out.append(i)
    return out


def leading_block(shape: Sequence[int], cap: int = BLOCK) -> int:
    """Rows along the leading axis of one block of a leaf of ``shape``:
    the largest divisor of the leading dimension whose block holds at most
    ``cap`` elements, and at least one row."""
    lead, rest = shape[0], int(np.prod(shape[1:]))
    return max(b for b in range(1, lead + 1)
               if lead % b == 0 and (b == 1 or b * rest <= cap))


def _q(x, scale, prec: str):
    """A block of a row in float32 as ``quantize`` gives it, with the
    row's float8 scale worked out over the whole row beforehand."""
    x = x.astype(jnp.float32)
    if prec == "f32":
        return x
    if prec != "fp8":
        raise ValueError(f"unknown precision {prec!r}")
    q = _round_e4m3(x / scale) * scale
    return x + (q - x)


@jax.jit
def _amax(x):
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


def _scales(rows, prec: str):
    """Each row's float8 scale (its largest magnitude over every leaf, as
    ``quantize`` takes it per row), or ones in float32."""
    if prec == "f32":
        return jnp.ones((len(rows),), jnp.float32)
    amax = jnp.stack([jnp.max(jnp.stack([_amax(x) for x in r])) for r in rows])
    return jnp.where(amax > 0, amax / FP8_MAX, 1.0)


@functools.partial(jax.jit, static_argnames=("size",))
def _slice(x, start, size: int):
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=0)


def _take(x, start: int, size: int):
    """Rows ``start`` to ``start + size`` of a leaf along its leading axis,
    cut where the leaf lives: a leaf in host memory sends only that block
    to the device."""
    if isinstance(x, np.ndarray):
        return x[start:start + size]
    return _slice(x, start, size)


@functools.partial(jax.jit, static_argnames=("prec",))
def _block(base, rows, scales, wn, alpha, *, prec: str):
    """One block: (the fused block in the base's dtype, each row's squared
    distance from the base over the block)."""
    b = base.astype(jnp.float32)
    q = [_q(r, scales[k], prec) for k, r in enumerate(rows)]
    avg = wn[0] * q[0]
    for k in range(1, len(q)):
        avg = avg + wn[k] * q[k]
    sq = jnp.stack([jnp.sum(jnp.square(x - b)) for x in q])
    return (b + alpha * (avg - b)).astype(base.dtype), sq


def fuse_tree(base, rows: Sequence, weights, alpha, *, prec: str = "f32"):
    """``fuse`` for a base pytree and K row pytrees of its structure:
    (the fused base as a pytree of the base's structure, sq [K] float64).
    Each block is the weighted mean of the rows' blocks, added one
    contribution at a time in float32; each distance is summed over the
    blocks in float64."""
    scales = _scales([jax.tree.leaves(r) for r in rows], prec)
    w = np.asarray(weights, np.float32)
    wn = jnp.asarray(w / np.sum(w, dtype=np.float32))
    a = jnp.float32(alpha)
    per_row = [jax.tree.leaves(r) for r in rows]
    out, parts = [], []
    for j, b in enumerate(jax.tree.leaves(base)):
        size = leading_block(b.shape, BLOCK)
        blocks = []
        for start in range(0, b.shape[0], size):
            fused, sq = _block(_take(b, start, size),
                               tuple(_take(r[j], start, size) for r in per_row),
                               scales, wn, a, prec=prec)
            blocks.append(fused)
            parts.append(sq)
        out.append(blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks))
    sq = np.sum(np.asarray(jax.device_get(parts), np.float64), axis=0)
    return jax.tree.unflatten(jax.tree.structure(base), out), sq
