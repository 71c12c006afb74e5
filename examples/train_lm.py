"""End-to-end training driver: train a ~100M-param qwen2.5-style model
for a few hundred steps through the full production stack (pipeline,
AdamW + cosine schedule, grad clipping, checkpointing, fault-tolerant
loop, straggler monitor).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

~100M params is CPU-heavy; --small trains the smoke config instead
(default here so the example completes in minutes).
"""

import argparse
import dataclasses
import logging

from repro.compile_cache import enable_compile_cache
from repro.configs import ModelConfig, RunConfig, ShapeConfig
from repro.launch.train import train


def hundred_m_config() -> ModelConfig:
    """A ~100M-param decoder-only config (qwen-style)."""
    return ModelConfig(
        name="qwen-100m", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv_heads=4, d_ff=2048, vocab_size=32000,
        qkv_bias=True, norm="rmsnorm", activation="swiglu",
        dtype="float32", attn_chunk=256, remat=False,
    )


def tiny_config() -> ModelConfig:
    return ModelConfig(
        name="qwen-tiny", family="dense", n_layers=4, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=384, vocab_size=4096,
        qkv_bias=True, norm="rmsnorm", activation="swiglu",
        dtype="float32", attn_chunk=128, remat=False,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--full-100m", action="store_true",
                    help="train the ~100M config (slow on 1 CPU core)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")

    cfg = hundred_m_config() if args.full_100m else tiny_config()
    n_params_est = cfg.param_count()
    print(f"training {cfg.name}: ~{n_params_est/1e6:.0f}M params, "
          f"{args.steps} steps @ batch {args.batch} x seq {args.seq}")

    run = RunConfig(steps=args.steps, lr=1e-3, warmup_steps=20,
                    checkpoint_dir=args.ckpt_dir, checkpoint_every=100,
                    log_every=20)
    shape = ShapeConfig("train", "train", args.seq, args.batch)
    state, info = train(cfg, run, shape=shape)
    print(f"loss: {info['losses'][0]:.3f} -> {info['losses'][-1]:.3f} "
          f"over {info['end_step']} steps "
          f"(recoveries={info['recoveries']}, "
          f"median step {info['median_step_s']:.2f}s)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
