"""Work counted from shapes alone: parameters, bytes and FLOPs.

These are the numerators of every roofline and MFU share the benchmark
prints.  They depend on the sizes in a configuration file and on the
traffic, never on how the program implements a step, so a later change
to the program cannot move them.
"""
from __future__ import annotations

from typing import Mapping


def _attn_width(cfg: Mapping) -> tuple:
    hd = cfg["head_dim"]
    return cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd


def layer_matmul_params(cfg: Mapping) -> int:
    """Weights of one encoder layer that enter a matrix multiplication:
    the four attention projections and the two MLP matrices."""
    d, f = cfg["d_model"], cfg["d_ff"]
    nq, nkv = _attn_width(cfg)
    return d * nq + 2 * d * nkv + nq * d + 2 * d * f


def body_params(cfg: Mapping) -> int:
    """Every parameter of the shared body (what a contribution carries):
    token and position embeddings, each layer's two LayerNorms and
    matrices, and the final LayerNorm."""
    d = cfg["d_model"]
    norm = 2 * d  # scale and bias
    per_layer = 2 * norm + layer_matmul_params(cfg)
    return (cfg["vocab_size"] * d + cfg["max_seq_len"] * d + norm
            + cfg["num_layers"] * per_layer)


def body_matmul_params(cfg: Mapping) -> int:
    return cfg["num_layers"] * layer_matmul_params(cfg)


def train_flops_per_step(cfg: Mapping, batch: int, seq: int,
                         num_classes: int) -> float:
    """Model FLOPs of one training step (forward and backward, 3x the
    forward): 6 per matmul weight per token in the body, the classifier
    head's two matrices once per sequence (it reads the mean-pooled
    state), and attention's scores and weighted values, 12 * L * S * width
    per token.  The embedding gather is not counted, and neither is any
    recomputation."""
    d = cfg["d_model"]
    nq, _ = _attn_width(cfg)
    tokens = batch * seq
    body = 6 * tokens * body_matmul_params(cfg)
    head = 6 * batch * (d * d + d * num_classes)
    attn = 12 * cfg["num_layers"] * seq * nq * tokens
    return float(body + head + attn)


def fuse_bytes(k: int, n: int, itemsize: int = 2) -> int:
    """HBM bytes a fuse of K rows of N elements must move at least: read
    the K rows and the base once, write the fused base once."""
    return (k + 2) * n * itemsize


def fuse_flops(k: int, n: int) -> float:
    """Arithmetic of the same fuse per element: the weighted sum (2K),
    each row's squared distance from the base (3K) and the damped
    update (3)."""
    return float(n * (5 * k + 3))


def fuse_required_s(k: int, n: int, peaks: Mapping, itemsize: int = 2) -> float:
    """The least time a chip needs for one fuse: whichever of its bytes
    and its FLOPs takes longer at the chip's peak."""
    return max(fuse_bytes(k, n, itemsize) / peaks["hbm_bytes_per_s"],
               fuse_flops(k, n) / peaks["bf16_flops_per_s"])
