"""Classifier finetuning driver (the paper's §4.3 finetuning procedure,
scaled to the tiny synthetic suite).

Used both (a) inside the ColD Fusion loop (each contributor finetunes the
base model on their dataset) and (b) for evaluation of a base model —
full finetuning or linear probing ("ColD-Frozen").

Jitted steps are cached per (config, num_classes, frozen, batch shape) so
the 30-iteration × many-contributor loops don't recompile.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.data.pipeline import batches
from repro.models import encoder as E
from repro.optim.optimizers import adamw, clip_by_global_norm, linear_decay_lr
from repro.train.losses import accuracy, cls_loss
from repro.utils import trace


@functools.lru_cache(maxsize=None)
def _steps(cfg: ArchConfig, num_classes: int, frozen: bool, lr: float, decay: float):
    opt = adamw(linear_decay_lr(lr, decay))

    def loss_fn(trainable, static_body, batch):
        body = trainable.get("body", static_body)
        logits = E.classify(cfg, body, trainable["head"], batch["tokens"])
        return cls_loss(logits, batch["labels"]), logits

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @jax.jit
    def train_step(trainable, opt_state, static_body, batch):
        (loss, logits), grads = grad_fn(trainable, static_body, batch)
        grads, _ = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, trainable)
        trainable = jax.tree.map(jnp.add, trainable, updates)
        return trainable, opt_state, loss, accuracy(logits, batch["labels"])

    @jax.jit
    def eval_step(body, head, batch):
        logits = E.classify(cfg, body, head, batch["tokens"])
        return jnp.sum((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.int32))

    return opt, train_step, eval_step


def finetune(
    cfg: ArchConfig,
    body,
    head,
    x: np.ndarray,
    y: np.ndarray,
    *,
    steps: int,
    batch_size: int = 32,
    lr: float = 5e-4,
    lr_decay: float = 0.0,
    frozen_body: bool = False,
    seed: int = 0,
) -> Tuple[Dict, Dict, Dict]:
    """Finetune (body, head) on (x, y).  Returns (body, head, metrics).

    ``frozen_body=True`` trains only the classification head — the paper's
    linear-probing evaluation (ColD-Frozen).
    """
    num_classes = int(head["out"].shape[-1])
    opt, train_step, _ = _steps(cfg, num_classes, frozen_body, lr, lr_decay)
    trainable = {"head": head} if frozen_body else {"head": head, "body": body}
    # a trained body reaches the step inside ``trainable``; passing it again
    # would only add its leaves to every dispatch
    static_body = body if frozen_body else None
    opt_state = opt.init(trainable)
    rng = np.random.default_rng(seed)
    losses, accs = [], []
    it = batches(x, y, batch_size, rng=rng, epochs=10_000)  # steps bound below
    # The host runs one step ahead of the device: step n is dispatched while
    # step n-1 runs, then the host waits for step n-1, so at most one step
    # is queued behind the running one (one extra copy of the state, no
    # more).  Losses and accuracies stay on the device until the end.
    for _ in range(steps):
        b = next(it)
        prev = losses[-1] if losses else None
        if prev is not None:
            trace.count("finetune.overlapped", not prev.is_ready())
        trainable, opt_state, loss, acc = train_step(trainable, opt_state,
                                                     static_body, b)
        trace.count("finetune.steps", 1)
        if prev is not None:
            prev.block_until_ready()
            trace.count("finetune.host_waits", 1)
        losses.append(loss)
        accs.append(acc)
    losses, accs = jax.device_get((losses, accs))
    trace.count("finetune.host_waits", 1)
    new_body = trainable.get("body", body)
    return new_body, trainable["head"], {"loss": [float(v) for v in losses],
                                         "train_acc": [float(v) for v in accs]}


def compute_fisher(
    cfg: ArchConfig, body, head, x: np.ndarray, y: np.ndarray,
    *, batches_n: int = 8, batch_size: int = 32, seed: int = 0,
):
    """Diagonal empirical Fisher of the body params (mean squared grad of the
    log-likelihood over minibatches) — the contributor-side statistic for
    Fisher-weighted fusion (Matena & Raffel 2021; paper §8 future work)."""
    from repro.train.losses import cls_loss

    def loss_fn(body, batch):
        logits = E.classify(cfg, body, head, batch["tokens"])
        return cls_loss(logits, batch["labels"])

    grad_fn = jax.jit(jax.grad(loss_fn))
    rng = np.random.default_rng(seed)
    fisher = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), body)
    for b in list(batches(x, y, batch_size, rng=rng))[:batches_n]:
        g = grad_fn(body, b)
        fisher = jax.tree.map(lambda f, gi: f + jnp.square(gi.astype(jnp.float32)), fisher, g)
    return jax.tree.map(lambda f: f / batches_n, fisher)


def evaluate(cfg: ArchConfig, body, head, x: np.ndarray, y: np.ndarray, batch_size: int = 64) -> float:
    num_classes = int(head["out"].shape[-1])
    _, _, eval_step = _steps(cfg, num_classes, False, 1e-3, 0.0)
    correct, total = 0, 0
    for b in batches(x, y, batch_size, drop_remainder=False):
        correct += int(eval_step(body, head, b))
        total += len(b["labels"])
    return correct / max(total, 1)
