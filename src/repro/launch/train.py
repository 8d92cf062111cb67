"""End-to-end LM training driver.

Trains any registered architecture (full or ``--reduced``) on the synthetic
token stream with the real train_step (remat, microbatching, optimizer from
the config).  On a multi-device runtime it builds the production mesh and
shards via `repro.launch.sharding`; on this CPU container it runs
single-device (the multi-device path is exercised by dryrun.py and the
subprocess tests).

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --reduced \
      --steps 200 --batch 8 --seq 64
  # the ~100M-parameter end-to-end run (paper-scale model, CPU-hours):
  PYTHONPATH=src python -m repro.launch.train --arch roberta-base --steps 300
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt
from repro.configs import ARCH_IDS, get_config, reduce_config
from repro.data.synthetic import SyntheticSuite
from repro.launch.compile_cache import enable_compile_cache
from repro.models import whisper as W
from repro.models.transformer import init_lm
from repro.optim.optimizers import make_optimizer, warmup_cosine_lr
from repro.train.step import make_train_state, make_train_step


def build_params(cfg, key):
    if cfg.is_encoder_decoder:
        return W.init_whisper(cfg, key, max_target_len=cfg.max_seq_len)
    return init_lm(cfg, key)


def main() -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=list(ARCH_IDS), default="gemma3-1b")
    p.add_argument("--reduced", action="store_true",
                   help="train the smoke-scale variant (CPU-friendly)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--save", default=None, help="checkpoint path (.npz)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.arch == "roberta-base":
        # decoder-style training of the encoder config: reuse the LM stack
        cfg = dataclasses.replace(cfg, rope=dataclasses.replace(cfg.rope, kind="default"))
    cfg = dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32",
                              remat=False, max_seq_len=max(cfg.max_seq_len, args.seq))

    key = jax.random.PRNGKey(args.seed)
    print(f"[train] {cfg.name}: ~{cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps x batch {args.batch} x seq {args.seq}")
    params = build_params(cfg, key)
    opt = make_optimizer(cfg.optimizer, warmup_cosine_lr(args.lr, warmup=20, total=args.steps))
    state = make_train_state(params, opt)
    step = jax.jit(make_train_step(cfg, opt, microbatches=args.microbatches))

    suite = SyntheticSuite(vocab_size=min(cfg.vocab_size, 512), num_tasks=8, seed=args.seed)
    stream = suite.lm_stream(args.steps * args.batch, args.seq, seed=args.seed)
    stream = np.clip(stream, 0, cfg.vocab_size - 1)

    t0 = time.time()
    for i in range(args.steps):
        toks = jnp.asarray(stream[i * args.batch : (i + 1) * args.batch])
        batch = {"tokens": toks}
        if cfg.rope.kind == "mrope":
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, None], (3, args.batch, args.seq))
        if cfg.family == "vlm" and cfg.num_frontend_tokens:
            batch["extra_embeds"] = jnp.zeros(
                (args.batch, cfg.num_frontend_tokens, cfg.d_model), jnp.float32)
        if cfg.is_encoder_decoder:
            batch["frames"] = jnp.zeros((args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        state, m = step(state, batch)
        if (i + 1) % args.log_every == 0 or i == 0:
            dt = (time.time() - t0) / (i + 1)
            print(f"  step {i+1:4d}: loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.2f} ({dt*1e3:.0f} ms/step)")
    print(f"[train] done in {time.time()-t0:.0f}s; final loss {float(m['loss']):.4f}")
    if args.save:
        ckpt.save(args.save, state["params"])
        print(f"[train] saved params to {args.save}")


if __name__ == "__main__":
    main()
