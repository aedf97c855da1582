"""Quickstart: build a small model, train briefly, serve with the
memory-processing pipeline (DSA sparse attention) — the 60-second tour.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data import TokenStream
from repro.models import init_params
from repro.serving import Engine, ServeConfig
from repro.train import OptConfig, TrainConfig, Trainer


def main():
    # 1) an assigned architecture, reduced for CPU
    cfg = get_arch("llama3.2-1b").smoke()
    print(f"arch={cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
          f"vocab={cfg.vocab_size} (padded {cfg.padded_vocab})")

    # 2) train a few steps (loss must drop on the structured synthetic data)
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4)
    tr = Trainer(cfg, TrainConfig(opt=OptConfig(lr=3e-3, warmup_steps=5,
                                                total_steps=100), tp=4),
                 params)
    ds = TokenStream(cfg.vocab_size, 64, 4, seed=0)
    for i, batch in zip(range(20), ds):
        stats = tr.train_step({k: jnp.asarray(v) for k, v in batch.items()})
        if i % 5 == 0:
            print(f"step {i:3d} loss {stats['loss']:.3f} "
                  f"lr {stats['lr']:.2e} |g| {stats['grad_norm']:.2f}")

    # 3) serve with the paper's memory pipeline (DeepSeek-style sparse
    #    attention with dynamic dense fallback below min_context)
    eng = Engine(cfg, tr.params,
                 ServeConfig(max_len=128, n_slots=4, method="dsa", tp=4,
                             page=8),
                 key=jax.random.PRNGKey(1))
    prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                                 cfg.vocab_size)
    out = eng.generate(prompts, max_new=8)
    print("generated tokens:\n", out)
    print(f"{eng.stats['decode_steps']} decode steps, "
          f"{eng.stats['tokens']} tokens")


if __name__ == "__main__":
    main()
