"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Runs the reduced config by default and the published widths with
``--full``, on the default device.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config
from repro.data import DataConfig, batches_for_model
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.optim import adamw, cosine_with_warmup
from repro.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="published widths (needs an accelerator); default reduced")
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = Model(cfg)
    print(f"{cfg.name}: {model.param_count()/1e6:.1f}M params on "
          f"{len(jax.devices())} device(s)")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch)
    opt = adamw(cosine_with_warmup(args.lr, max(args.steps // 10, 1), args.steps))
    train(model, opt, batches_for_model(cfg, dc), args.steps,
          log_every=max(args.steps // 10, 1),
          ckpt_dir=args.ckpt_dir or None,
          ckpt_every=args.steps if args.ckpt_dir else 0)


if __name__ == "__main__":
    main()
