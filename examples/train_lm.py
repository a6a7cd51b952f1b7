"""End-to-end LM training with MARINA-P downlink compression.

Trains a ~100M-parameter gemma-family model for a few hundred steps on the
synthetic token pipeline, with the paper's compressed server->worker model
broadcast as a first-class feature, and checkpoints at the end.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 300
(defaults are sized for the CPU container; --steps 300 takes a while — use
--steps 30 for a smoke run.)
"""
import argparse
import time

import jax

from repro.data import SyntheticLMData
from repro.checkpoint import save_checkpoint
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig, uniform_pattern
from repro.optim import make_optimizer
from repro.optim.schedules import cosine_warmup
from repro.train import TrainerConfig, init_state, make_downlink, make_train_step


def model_100m(layers=8, d_model=768):
    """~100M params, gemma-flavoured (GeGLU, MQA)."""
    return ModelConfig(
        arch_id="demo-100m", family="dense", num_layers=layers, d_model=d_model,
        num_heads=12, num_kv_heads=4, head_dim=64, d_ff=3072, vocab_size=32768,
        block_pattern=uniform_pattern("attn", layers), mlp_kind="geglu",
    )


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch-per-worker", type=int, default=2)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--downlink", default="marina:perm",
                    help="marina:perm|marina:ind|marina:same|ef21p:128:1024|none")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--ckpt", default="runs/train_lm_ckpt.npz")
    args = ap.parse_args()

    cfg = model_100m(args.layers, args.d_model)
    from repro.models import lm
    print(f"model: {lm.count_params(cfg)/1e6:.1f}M params, downlink={args.downlink}")

    tcfg = TrainerConfig(n_workers=args.workers, attn_chunk=128)
    downlink = make_downlink(args.downlink, args.workers)
    optimizer = make_optimizer("adamw", weight_decay=0.01)
    lr = cosine_warmup(3e-4, warmup=min(50, args.steps // 4), total=args.steps)
    state = init_state(cfg, tcfg, downlink, optimizer, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, tcfg, downlink, optimizer, lr), donate_argnums=0)
    data = SyntheticLMData(cfg, args.workers, args.batch_per_worker, args.seq)

    t0 = time.time()
    for i in range(args.steps):
        state, m = step(state, data.batch(i), jax.random.fold_in(jax.random.PRNGKey(7), i))
        if i % 10 == 0 or i == args.steps - 1:
            dt = time.time() - t0
            print(f"step {i:4d} loss={float(m['loss']):.4f} lr={float(m['lr']):.2e} "
                  f"gnorm={float(m['grad_norm']):.2f} drift={float(m.get('drift', 0)):.3e} "
                  f"bits/w={float(m['bits_per_worker']):.2e} ({dt:.0f}s)")
    save_checkpoint(args.ckpt, state["server"], step=args.steps,
                    extra={"arch": cfg.arch_id, "downlink": args.downlink})
    print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()
