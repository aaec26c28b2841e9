"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant training loop (repro.train.loop) on the local
device topology. On real hardware the same entry point runs under
``jax.distributed.initialize`` (one process per host); in this container it
drives CPU-sized reduced configs end-to-end — see
``examples/train_lm.py`` for the ~100M-parameter run.
"""

from __future__ import annotations

import argparse

import jax

from repro import obs
from repro.configs import get_config
from repro.data import MarkovLMDataset, make_batch_fn
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamWConfig
from repro.train import TrainLoopConfig, train


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="CPU-sized config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family in ("vlm", "audio"):
        raise SystemExit(
            "train launcher drives LM-family archs; vlm/audio are covered by "
            "their smoke tests and the dry-run"
        )

    ds = MarkovLMDataset(
        vocab=cfg.vocab, seq_len=args.seq, batch=args.batch, seed=args.seed
    )
    opt = AdamWConfig(
        peak_lr=args.lr, warmup_steps=max(10, args.steps // 20),
        total_steps=args.steps,
    )
    loop = TrainLoopConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        log_every=max(1, args.steps // 20),
    )
    log = obs.get_logger("train")
    res = train(
        cfg, opt, loop, make_batch_fn(ds),
        init_key=jax.random.key(args.seed), log=log.raw,
    )
    log.info(
        "done", loss_first=res.losses[0], loss_last=res.losses[-1],
        stragglers=res.straggler_steps,
    )


if __name__ == "__main__":
    main()
