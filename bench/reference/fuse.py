"""Plain reference of a ColD Fusion round (paper section 3, with the
section 9 screen) over flat contribution rows.

fused = base + alpha * (sum_k w_k row_k / sum_k w_k - base), rounded to the
row dtype; sq[k] = ||row_k - base||^2; the screen rejects non-finite and
zero distances and, in a cohort of three or more, any norm above
median + t * max(MAD, 0.05 * median).  Arithmetic in float32;
``prec="fp8"`` first rounds every row to float8 e4m3 (per-row scale): the
control.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .common import quantize


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
