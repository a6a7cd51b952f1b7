"""Batched serving demo: prefill + generate over the decode engine.

Loads a checkpoint if present (e.g. from examples/train_lm.py), otherwise
random-initializes, then serves a batch of prompts with greedy and sampled
decoding.

Run:  PYTHONPATH=src python examples/serve_lm.py --batch 4 --new-tokens 32
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import load_checkpoint
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve import DecodeEngine, greedy_sample, temperature_sample

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from train_lm import model_100m  # noqa: E402 (same directory)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default="runs/train_lm_ckpt.npz")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--temp", type=float, default=0.0, help="0 = greedy")
    args = ap.parse_args()

    cfg = model_100m(args.layers, args.d_model)
    params = lm.lm_init(cfg, jax.random.PRNGKey(0))
    if os.path.exists(args.ckpt):
        params, meta = load_checkpoint(args.ckpt, params)
        print(f"loaded {args.ckpt} (step {meta['step']})")
    else:
        print("no checkpoint found — serving random init")

    engine = DecodeEngine(
        cfg, params,
        cache_len=args.prompt_len + args.new_tokens,
        batch_size=args.batch,
        sample_fn=temperature_sample(args.temp) if args.temp > 0 else greedy_sample,
    )
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    t0 = time.time()
    toks = engine.run(prompts, n_new_tokens=args.new_tokens)
    dt = time.time() - t0
    total = args.batch * args.new_tokens
    print(f"generated {total} tokens in {dt:.1f}s ({total/dt:.1f} tok/s, batch={args.batch})")
    for b in range(min(args.batch, 2)):
        print(f"  req{b}: {list(map(int, toks[b][:16]))} ...")


if __name__ == "__main__":
    main()
