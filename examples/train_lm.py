"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

  PYTHONPATH=src:. python examples/train_lm.py [--steps 300]

Uses the production stack end to end: config registry (smollm-135m family,
width-reduced to fit CPU time), synthetic data pipeline with prefetch,
AdamW + cosine schedule, checkpoint/restart manager, MaRe-tree gradient
sync when multiple devices are present.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.data import Prefetcher, SyntheticText, lm_batches
from repro.models import build_model, param_count
from repro.optim import adamw
from repro.optim.schedule import cosine_warmup
from repro.train import (StepConfig, Trainer, TrainerConfig,
                         init_train_state, make_train_step)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = get_smoke_config("smollm-135m").scaled(
        num_layers=4, d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=256, vocab_size=512)
    model = build_model(cfg)
    opt = adamw()
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    print(f"params: {param_count(state.params)/1e6:.2f}M "
          f"(reduced {cfg.name} family)")

    src = SyntheticText(cfg.vocab_size, doc_len=512, seed=0)
    pf = Prefetcher(lambda: lm_batches(src, args.batch, args.seq,
                                       cfg.vocab_size),
                    capacity=4, deadline_s=5.0)
    cached = [next(pf) for _ in range(32)]
    pf.close()

    def batch_fn(i):
        return {k: jnp.asarray(v) for k, v in cached[i % 32].items()}

    step = jax.jit(make_train_step(
        model, opt, cosine_warmup(3e-3, 20, args.steps), StepConfig()))
    with tempfile.TemporaryDirectory() as d:
        trainer = Trainer(step, state, None, CheckpointManager(d),
                          TrainerConfig(total_steps=args.steps,
                                        checkpoint_every=100,
                                        log_every=20),
                          batch_fn=batch_fn)
        trainer.run()
    first, last = trainer.history[0]["loss"], trainer.history[-1]["loss"]
    print(f"loss: {first:.3f} -> {last:.3f}")
    assert last < first, "training failed to reduce loss"
    print("OK")


if __name__ == "__main__":
    main()
