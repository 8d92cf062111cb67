"""Batched serving driver: loads (or random-inits) a model, prefills a batch
of synthetic prompts, and greedy-decodes with the KV-cache engine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --batch 4 --prompt-len 12 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import io as ckpt
from repro.configs import ARCH_IDS, get_config, reduce_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_lm
from repro.serve.engine import Engine


def main() -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=list(ARCH_IDS), default="gemma3-1b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--load", default=None, help="params checkpoint (.npz)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if cfg.is_encoder_decoder:
        raise SystemExit("use whisper_decode directly for enc-dec archs")
    key = jax.random.PRNGKey(args.seed)
    params = ckpt.load(args.load) if args.load else init_lm(cfg, key)
    max_len = args.prompt_len + args.new_tokens + 1
    eng = Engine(cfg, params, max_len=max_len)
    prompts = np.asarray(
        jax.random.randint(key, (args.batch, args.prompt_len), 3, cfg.vocab_size)
    )
    t0 = time.time()
    res = eng.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.time() - t0
    print(f"[serve] {cfg.name}: {args.batch} requests x {args.new_tokens} tokens "
          f"in {dt:.2f}s ({args.batch*args.new_tokens/dt:.1f} tok/s)")
    for i, row in enumerate(res.tokens):
        print(f"  req{i}: {row[: res.prompt_len].tolist()} -> {row[res.prompt_len:].tolist()}")


if __name__ == "__main__":
    main()
