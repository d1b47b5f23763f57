"""Training launcher.

CPU-scale usage (runs real steps on reduced configs):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --batch 8 --seq 64

Production usage (requires a real TPU mesh; on CPU use --dry-run, which
lowers/compiles only — see repro.launch.dryrun for the full sweep):
  python -m repro.launch.train --arch gemma-7b --shape train_4k --mesh 16x16
"""
from __future__ import annotations

import argparse

import jax

from ..configs import get_config
from ..data import lm_batches
from ..models import Model
from ..training import OptConfig, save, train
from .compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if not args.smoke and jax.default_backend() == "cpu":
        raise SystemExit(
            "full configs need a TPU mesh; use --smoke on CPU or the dry-run "
            "(python -m repro.launch.dryrun) for lowering/compile validation"
        )
    model = Model(cfg)
    embeds_dim = cfg.d_model if cfg.input_mode == "embeds" else None
    batches = lm_batches(
        cfg.vocab_size, args.batch, args.seq, seed=args.seed, embeds_dim=embeds_dim
    )
    opt = OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20), total_steps=args.steps)
    res = train(model, batches, args.steps, opt, seed=args.seed, log_every=args.log_every)
    if args.checkpoint:
        save(args.checkpoint, res.params)
        print(f"saved checkpoint to {args.checkpoint}")
    first, last = res.history[0]["loss"], res.history[-1]["loss"]
    print(f"final: loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
