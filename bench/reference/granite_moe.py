"""Plain reference of the body of a GraniteMoE decoder, as the program lays
it out (``repro.models.transformer.init_lm`` for a pattern of one block,
GQA attention and a mixture of experts):

    embed                      [vocab, d]
    final_norm/scale           [d]
    scan/pos0/norm1/scale      [L, d]            (norm2 alike)
    scan/pos0/attn/wq          [L, d, H * hd]    (wo: [L, H * hd, d])
    scan/pos0/attn/wk          [L, d, KV * hd]   (wv alike)
    scan/pos0/moe/router       [L, d, E]
    scan/pos0/moe/w_gate       [L, E, d, f]      (w_up alike)
    scan/pos0/moe/w_down       [L, E, f, d]

with ``lm_head`` [d, vocab] when the embeddings are not tied and a
``bias`` beside each ``scale`` under LayerNorm.  Layers are stacked on a
leading axis, as the program scans them; ``tail`` is empty since the
pattern has one block.  The fuse is blind to what the leaves mean, so this
module gives the seeded body and its parameter count, and no forward pass.
"""
from __future__ import annotations

import functools
from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Sizes(NamedTuple):
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    num_experts: int
    vocab_size: int
    tie_embeddings: bool
    layernorm: bool

    @classmethod
    def of(cls, cfg: Mapping) -> "Sizes":
        return cls(cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
                   cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"],
                   cfg["moe"]["num_experts"], cfg["vocab_size"],
                   bool(cfg["tie_embeddings"]), cfg["norm"] == "layernorm")


def _shapes(sz: Sizes):
    """Every leaf's shape and its initialiser ("dense" with its fan-in,
    "embed", "ones", "zeros"), in the body's nesting."""
    L, d, f, E = sz.num_layers, sz.d_model, sz.d_ff, sz.num_experts
    q, kv = sz.num_heads * sz.head_dim, sz.num_kv_heads * sz.head_dim

    def norm(*lead):
        p = {"scale": ((*lead, d), "ones")}
        if sz.layernorm:
            p["bias"] = ((*lead, d), "zeros")
        return p

    body = {
        "embed": ((sz.vocab_size, d), "embed"),
        "final_norm": norm(),
        "scan": {"pos0": {
            "norm1": norm(L), "norm2": norm(L),
            "attn": {"wq": ((L, d, q), d), "wk": ((L, d, kv), d),
                     "wv": ((L, d, kv), d), "wo": ((L, q, d), q)},
            "moe": {"router": ((L, d, E), d), "w_gate": ((L, E, d, f), d),
                    "w_up": ((L, E, d, f), d), "w_down": ((L, E, f, d), f)},
        }},
        "tail": {},
    }
    if not sz.tie_embeddings:
        body["lm_head"] = ((d, sz.vocab_size), d)
    return body


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _init(key, *, sz: Sizes, dtype=jnp.bfloat16):
    specs, treedef = jax.tree.flatten(_shapes(sz), is_leaf=_is_spec)
    keys = jax.random.split(key, len(specs))
    out = []
    for k, (shape, how) in zip(keys, specs):
        if how == "ones":
            out.append(jnp.ones(shape, dtype))
        elif how == "zeros":
            out.append(jnp.zeros(shape, dtype))
        else:
            # matrices N(0, 1/fan_in), the embedding N(0, 0.02**2)
            scale = 0.02 if how == "embed" else 1.0 / np.sqrt(how)
            out.append((jax.random.normal(k, shape, jnp.float32)
                        * scale).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def init_body(key, as_run: Mapping):
    """The seeded body in one jitted call, in the configuration's dtype."""
    return _init(key, sz=Sizes.of(as_run),
                 dtype=jnp.dtype(as_run["param_dtype"]))


def body_params(as_run: Mapping) -> int:
    """Parameters of that body, counted from the shapes alone."""
    specs = jax.tree.leaves(_shapes(Sizes.of(as_run)), is_leaf=_is_spec)
    return sum(int(np.prod(shape)) for shape, _ in specs)
