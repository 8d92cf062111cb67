"""Seeds, quantization and comparisons shared by the references."""
from __future__ import annotations

from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def quantize(x: jax.Array, prec: str) -> jax.Array:
    """``x`` in float32 as the precision ``prec`` holds it: unchanged for
    "f32"; for "fp8" scaled per tensor to the float8 e4m3 range, rounded
    to it and scaled back (the control's precision).  The rounding passes
    gradients straight through, so a backward pass sees the rounded
    operands with float32 cotangents."""
    x = x.astype(jnp.float32)
    if prec == "f32":
        return x
    if prec != "fp8":
        raise ValueError(f"unknown precision {prec!r}")
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = _round_e4m3(x / s) * s
    return x + jax.lax.stop_gradient(q - x)


def _round_e4m3(y):
    """``y`` (within +-448) rounded to the nearest float8 e4m3 value, ties
    to even: 3 mantissa bits, subnormal steps of 2**-9.  Written as
    arithmetic rather than a round trip through the float8 type, which XLA
    may drop where it allows excess precision."""
    _, e = jnp.frexp(y)  # y = m * 2**e with 0.5 <= |m| < 1
    step = jnp.exp2(jnp.maximum(e.astype(jnp.float32) - 1.0, -6.0) - 3.0)
    return jnp.clip(jnp.round(y / step) * step, -FP8_MAX, FP8_MAX)


def path_str(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def named_leaves(tree) -> Dict[str, jax.Array]:
    return {path_str(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def flat(tree) -> jax.Array:
    """Every leaf raveled and concatenated in pytree order: the layout of
    a contribution row."""
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def sample_index(seed: int, tree, k: int = 4096):
    """Per leaf of ``tree``, ``k`` flat indices drawn from the seed (all of
    a leaf that has no more than ``k`` elements)."""
    rng = np.random.default_rng([int(seed), 0x5A4D])
    return jax.tree.map(
        lambda a: (np.arange(a.size, dtype=np.int32) if a.size <= k else
                   np.sort(rng.integers(0, a.size, k)).astype(np.int32)),
        tree)


@jax.jit
def take_samples(tree, index):
    """The elements of each leaf at ``sample_index``'s indices, in float32."""
    return jax.tree.map(lambda a, i: jnp.ravel(a)[i].astype(jnp.float32),
                        tree, index)


@jax.jit
def change_norms(new, old):
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), new, old)


@jax.jit
def max_bf16_steps(got: jax.Array, want: jax.Array) -> jax.Array:
    """Largest |got - want| over the elements, in bf16 rounding steps at the
    larger of the two magnitudes; inf where either is not finite."""
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    m = jnp.maximum(jnp.abs(g), jnp.abs(w))
    e = jnp.floor(jnp.log2(jnp.maximum(m, 2.0 ** -133)))
    step = jnp.exp2(jnp.maximum(e, -126.0) - 7.0)
    d = jnp.abs(g - w)
    steps = jnp.where(d == 0, 0.0, d / step)
    bad = ~(jnp.isfinite(g) & jnp.isfinite(w))
    return jnp.max(jnp.where(bad, jnp.inf, steps))


def _kept(want: Mapping, grad: Mapping[str, float]):
    """The leaves that count: those whose reference gradient is at least a
    thousandth of the median leaf's (the others move by round-off alone)."""
    g_med = float(np.median([grad[k] for k in grad]))
    return [k for k in want if grad.get(k, 0.0) >= 1e-3 * g_med]


def _rms(x) -> float:
    return float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))


def _relative(num: Dict[str, float], den: Dict[str, float]) -> Dict[str, float]:
    """``num[k]`` over the larger of ``den[k]`` and the median of ``den``;
    inf where that is not finite."""
    med = float(np.median(list(den.values()))) if den else 0.0
    out = {}
    for k, n in num.items():
        d = max(den[k], med)
        r = n / d if d > 0 else float(n != 0)
        out[k] = r if np.isfinite(r) else float("inf")
    return out


def leaf_gaps(got: Mapping[str, float], want: Mapping[str, float],
              grad: Mapping[str, float]) -> Dict[str, float]:
    """Each kept leaf's gap between two per-leaf norms, |got - want|, over
    the reference's norm of that leaf or of the median leaf, whichever is
    larger.  A leaf missing on either side reads inf."""
    if set(got) != set(want):
        return {"missing": float("inf")}
    keep = _kept(want, grad)
    return _relative({k: abs(got[k] - want[k]) for k in keep},
                     {k: want[k] for k in keep})


def leaf_errors(got: Mapping[str, np.ndarray], want: Mapping[str, np.ndarray],
                grad: Mapping[str, float]) -> Dict[str, float]:
    """Each kept leaf's elementwise error on its sample: the root mean
    square of ``got - want`` over the larger of that leaf's and the median
    leaf's root mean square of ``want``."""
    if set(got) != set(want):
        return {"missing": float("inf")}
    keep = _kept(want, grad)
    return _relative(
        {k: _rms(np.asarray(got[k], np.float64) - np.asarray(want[k], np.float64))
         for k in keep},
        {k: _rms(want[k]) for k in keep})


def worst_leaf_gap(got: Mapping[str, float], want: Mapping[str, float],
                   grad: Mapping[str, float]) -> float:
    """The largest of ``leaf_gaps``."""
    return max(leaf_gaps(got, want, grad).values(), default=0.0)
