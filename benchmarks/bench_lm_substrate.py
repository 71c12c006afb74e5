"""LM-substrate micro-benchmarks (framework-side tables): per-arch smoke
train-step latency and decode-step latency on CPU (reduced configs) —
regression guards for the substrate, not roofline numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import RunConfig, ShapeConfig, get_smoke_config, \
    list_archs
from repro.launch.train import init_state, make_train_step
from repro.models import build_model

from .common import emit, time_fn

SHAPE = ShapeConfig("bench", "train", 32, 2)


def run(archs=None):
    archs = archs or ["qwen2.5-3b", "granite-moe-1b-a400m",
                      "recurrentgemma-9b", "rwkv6-3b"]
    for arch in archs:
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        state = init_state(model, RunConfig(seed=0))
        batch = model.dummy_batch(SHAPE)
        step = jax.jit(make_train_step(model, RunConfig(),
                                       total_steps=100))
        t = time_fn(lambda: step(state, batch)[1]["loss"])
        tok_s = SHAPE.tokens_per_step / t
        emit(f"lm_train/{arch}", t * 1e6, f"tokens_per_s={tok_s:.0f}")

        pre = dict(batch)
        pre["tokens"] = batch["tokens"][:, :16]
        logits, cache, pos = model.prefill(state.params, pre, 64)
        dec = jax.jit(lambda p, c, t_, q: model.decode_step(p, c, t_, q))
        tok = batch["tokens"][:, :1]
        t = time_fn(lambda: dec(state.params, cache, tok,
                                jnp.int32(16))[0])
        emit(f"lm_decode/{arch}", t * 1e6,
             f"tok_per_s={SHAPE.global_batch / t:.0f}")


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
