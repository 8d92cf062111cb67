"""Plain reference of the encoder classifier the configurations run.

It follows the program's stated architecture, which departs from the
published RoBERTa in five ways, kept here so that the comparison checks
what the program claims to compute:

* LayerNorm before each sublayer (pre-LN), not after it;
* no biases in the attention and MLP projections, and no token-type
  embedding or position offset;
* GELU in its tanh form;
* no attention mask (every sequence is full length);
* the classifier reads the mean-pooled final state:
  ``logits = tanh(mean(h) @ dense) @ out + bias``.

Everything is computed in float32 with matmuls at ``Precision.HIGHEST``.
Parameters are stored in the configuration's dtype (bfloat16) between
steps, as the configuration states; each step's update is computed in
float32 and rounded once into that storage.  ``prec="fp8"`` quantizes both
operands of every matmul to float8 e4m3 (per-tensor scale): the control.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts
from .common import HIGHEST, leaf_norms, quantize, take_samples


class Sizes(NamedTuple):
    num_layers: int
    d_model: int
    num_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    max_seq_len: int
    norm_eps: float
    num_classes: int

    @classmethod
    def of(cls, cfg, num_classes: int) -> "Sizes":
        return cls(cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
                   cfg["head_dim"], cfg["d_ff"], cfg["vocab_size"],
                   cfg["max_seq_len"], float(cfg["norm_eps"]), num_classes)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def init_params(key, *, sz: Sizes, dtype=jnp.bfloat16):
    """Seeded weights in one jitted call, in the layout the program reads:
    ``{"body": ..., "head": ...}``.  Matrices are N(0, 1/fan_in), the
    embeddings N(0, 0.02**2), LayerNorms one and zero."""
    L, d, f = sz.num_layers, sz.d_model, sz.d_ff
    w = sz.num_heads * sz.head_dim
    ks = iter(jax.random.split(key, 6 * L + 5))

    def dense(n_in, n_out):
        return (jax.random.normal(next(ks), (n_in, n_out), jnp.float32)
                / np.sqrt(n_in)).astype(dtype)

    def norm():
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}

    body = {
        "embed": (jax.random.normal(next(ks), (sz.vocab_size, d)) * 0.02).astype(dtype),
        "pos": (jax.random.normal(next(ks), (sz.max_seq_len, d)) * 0.02).astype(dtype),
        "final_norm": norm(),
        "layers": {},
    }
    for i in range(L):
        body["layers"][f"layer{i}"] = {
            "norm1": norm(),
            "attn": {"wq": dense(d, w), "wk": dense(d, w), "wv": dense(d, w),
                     "wo": dense(w, d)},
            "norm2": norm(),
            "mlp": {"w_up": dense(d, f), "w_down": dense(f, d)},
        }
    head = {"dense": dense(d, d), "out": dense(d, sz.num_classes),
            "bias": jnp.zeros((sz.num_classes,), dtype)}
    return {"body": body, "head": head}


def init_body(key, as_run):
    """The seeded shared body (what a contribution carries) of a
    configuration file's ``as_run``: ``init_params``'s ``body``, so the same
    seed gives the same bytes as the finetune cells' starting weights."""
    return init_params(key, sz=Sizes.of(as_run, 2))["body"]


def body_params(as_run) -> int:
    """Parameters of that body (``counts.body_params``)."""
    return counts.body_params(as_run)


def _mm(a, b, prec):
    return jnp.matmul(quantize(a, prec), quantize(b, prec), precision=HIGHEST)


def _ln(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _layer(sz: Sizes, prec: str, p, x):
    B, S, _ = x.shape
    H, hd = sz.num_heads, sz.head_dim
    h = _ln(p["norm1"], x, sz.norm_eps)
    q = _mm(h, p["attn"]["wq"], prec).reshape(B, S, H, hd)
    k = _mm(h, p["attn"]["wk"], prec).reshape(B, S, H, hd)
    v = _mm(h, p["attn"]["wv"], prec).reshape(B, S, H, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", quantize(q, prec), quantize(k, prec),
                   precision=HIGHEST) / np.sqrt(hd)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", quantize(a, prec), quantize(v, prec),
                   precision=HIGHEST).reshape(B, S, H * hd)
    x = x + _mm(o, p["attn"]["wo"], prec)
    h2 = _ln(p["norm2"], x, sz.norm_eps)
    u = jax.nn.gelu(_mm(h2, p["mlp"]["w_up"], prec), approximate=True)
    return x + _mm(u, p["mlp"]["w_down"], prec)


def logits(sz: Sizes, params, tokens, prec: str = "f32"):
    """[B, S] token ids -> [B, C] logits, all in float32."""
    body = jax.tree.map(lambda a: a.astype(jnp.float32), params["body"])
    head = jax.tree.map(lambda a: a.astype(jnp.float32), params["head"])
    S = tokens.shape[1]
    x = body["embed"][tokens] + body["pos"][None, :S]
    # one layer's program, scanned over the stacked layers (an unrolled
    # float32 stack compiles to an executable too large to cache)
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *[
        body["layers"][f"layer{i}"] for i in range(sz.num_layers)])
    layer = jax.checkpoint(functools.partial(_layer, sz, prec))
    x, _ = jax.lax.scan(lambda c, p: (layer(p, c), None), x, stacked)
    h = _ln(body["final_norm"], x, sz.norm_eps)
    pooled = jnp.tanh(_mm(jnp.mean(h, axis=1), head["dense"], prec))
    return _mm(pooled, head["out"], prec) + head["bias"]


def loss(sz: Sizes, params, batch, prec: str = "f32"):
    z = logits(sz, params, batch["tokens"], prec)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


class AdamW(NamedTuple):
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: float


@functools.partial(jax.jit, static_argnames=("sz", "hp", "prec"))
def _step(params, m, v, t, batch, index, *, sz: Sizes, hp: AdamW, prec: str):
    """One AdamW step with global-norm clipping.  ``params`` are in their
    storage dtype; returns the new ones rounded into it, the moments, the
    loss, and the clipped gradient's per-leaf norms and its elements at
    ``index`` (a pytree of flat indices, as ``common.sample_index``)."""
    pf = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    val, g = jax.value_and_grad(lambda p: loss(sz, p, batch, prec))(pf)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, hp.clip_norm / (gn + 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    m = jax.tree.map(lambda a, x: hp.b1 * a + (1 - hp.b1) * x, m, g)
    v = jax.tree.map(lambda a, x: hp.b2 * a + (1 - hp.b2) * x * x, v, g)
    bc1 = 1 - hp.b1 ** t
    bc2 = 1 - hp.b2 ** t

    def upd(p, p32, a, b):
        u = (a / bc1) / (jnp.sqrt(b / bc2) + hp.eps) + hp.weight_decay * p32
        return (p32 - hp.lr * u).astype(p.dtype)

    new = jax.tree.map(upd, params, pf, m, v)
    return new, m, v, val, leaf_norms(g), take_samples(g, index)


def train_steps(sz: Sizes, hp: AdamW, params0, batches: Sequence[Dict],
                index, prec: str = "f32"):
    """Run ``len(batches)`` steps from ``params0``.  Returns the loss of
    each step, the per-leaf norms of the first step's clipped gradient (as
    the optimizer receives it) and its elements at ``index``, and the
    parameters after the last step."""
    zeros = lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params0)
    params, m, v = params0, zeros(), zeros()
    losses: List[float] = []
    first = None
    for t, b in enumerate(batches, 1):
        b = {"tokens": jnp.asarray(b["tokens"], jnp.int32),
             "labels": jnp.asarray(b["labels"], jnp.int32)}
        params, m, v, val, gnorm, gsample = _step(
            params, m, v, jnp.float32(t), b, index, sz=sz, hp=hp, prec=prec)
        losses.append(float(val))
        if first is None:
            first = gnorm, jax.device_get(gsample)
    return losses, first[0], first[1], params
