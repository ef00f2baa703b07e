"""End-to-end training driver (deliverable (b)): train a language model for
a few hundred steps with the full stack — data pipeline, FMI-mode
distribution, AdamW, checkpointing — and verify the loss drops.

Default (CPU-container-sized):
    PYTHONPATH=src python examples/train_lm.py
    # ~15M-param llama-family model, 200 steps, fmi-mode on 1 device

The ~100M configuration (same code, more compute):
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim.optimizer import OptConfig
from repro.training.train_step import TrainConfig, init_opt_state, make_train_step

PRESETS = {
    # ~15M params: quick on a single CPU core
    "small": dict(n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024,
                  vocab_size=4096, head_dim=32),
    # ~100M params: the deliverable-scale run (use when cores allow)
    "100m": dict(n_layers=8, d_model=768, n_heads=12, n_kv_heads=4, d_ff=3072,
                 vocab_size=16384, head_dim=64),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=list(PRESETS), default="small")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", choices=["xla", "fmi"], default="xla")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = configs.get("llama3.2-1b").reduced(**PRESETS[args.preset])
    n = lm.count_params(cfg)
    print(f"model: {n/1e6:.1f}M params ({args.preset}), {args.steps} steps, "
          f"batch {args.batch} x seq {args.seq}, mode={args.mode}")

    mesh = make_host_mesh(1, 1)
    tcfg = TrainConfig(
        mode=args.mode,
        optimizer=OptConfig(lr=6e-4, warmup_steps=20, total_steps=args.steps),
    )
    step_fn, _, _ = make_train_step(cfg, tcfg, mesh, multi_pod=False)
    dcfg = DataConfig()
    ckpt = CheckpointManager(args.ckpt_dir)

    with jax.set_mesh(mesh):
        params = lm.init_params(cfg, jax.random.key(0))
        opt = init_opt_state(cfg, tcfg, params)
        losses, t0 = [], time.perf_counter()
        for step in range(args.steps):
            batch = jax.tree.map(
                jnp.asarray, synthetic_batch(dcfg, cfg, args.batch, args.seq, step)
            )
            params, opt, m = step_fn(params, opt, batch)
            losses.append(float(m["ce"]))
            if step % 20 == 0 or step == args.steps - 1:
                tokps = args.batch * args.seq * (step + 1) / (time.perf_counter() - t0)
                print(f"step {step:4d}  ce {losses[-1]:.4f}  lr {float(m['lr']):.2e}"
                      f"  {tokps:,.0f} tok/s")
            if (step + 1) % 100 == 0:
                ckpt.save_async({"params": params, "opt": opt}, step + 1)
        ckpt.wait()

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"\nce: {first:.3f} -> {last:.3f} "
          f"({'LEARNING OK' if last < first - 0.3 else 'no material drop'})")


if __name__ == "__main__":
    main()
