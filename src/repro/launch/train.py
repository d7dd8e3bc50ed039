"""Production training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 200 --batch 8 --seq 256 [--scale full|smoke|100m] \
      --abft auto|global|block_1s|off [--ckpt-dir /tmp/ck]

Single-host it runs on local devices; on a real cluster the same driver is
launched per host after jax.distributed.initialize (flag --distributed).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get_config, scaled_down
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import abft_config
from repro.models import build_model
from repro.models.counting import count_params
from repro.train import OptConfig, TrainConfig
from repro.train.trainer import Trainer, TrainerConfig


def scale_config(cfg, scale: str):
    if scale == "full":
        return cfg
    if scale == "smoke":
        return scaled_down(cfg)
    if scale == "100m":
        # ~100M-param member of the same family (example (b) driver)
        return scaled_down(
            cfg, d_model=768, n_layers=12, n_heads=12,
            n_kv_heads=min(cfg.n_kv_heads, 12) if cfg.n_kv_heads else 0,
            head_dim=64, d_ff=2048, vocab_size=32768)
    raise ValueError(scale)


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="llama3.2-1b")
    ap.add_argument("--scale", choices=["full", "smoke", "100m"],
                    default="smoke")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--abft", default="auto",
                    choices=["auto", "global", "block_1s", "off"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args(argv)

    if args.distributed:
        jax.distributed.initialize()

    cfg = scale_config(get_config(args.arch), args.scale)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    n_params = count_params(cfg)
    print(f"arch={cfg.name} scale={args.scale} params~{n_params/1e6:.1f}M "
          f"abft={args.abft}")

    tcfg = TrainConfig(opt=OptConfig(lr=args.lr),
                       microbatches=args.microbatches)
    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                      vocab_size=cfg.vocab_size)
    rcfg = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir)
    trainer = Trainer(model, params, tcfg, dcfg, rcfg,
                      abft=abft_config(args.abft))
    if args.resume:
        trainer.maybe_restore()

    t0 = time.perf_counter()
    hist = trainer.run()
    dt = time.perf_counter() - t0
    toks = args.steps * args.batch * args.seq
    print(json.dumps({
        "first_loss": hist[0]["loss"] if hist else None,
        "last_loss": hist[-1]["loss"] if hist else None,
        "steps": len(hist),
        "tokens_per_s": toks / dt,
        "events": trainer.events,
    }, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
