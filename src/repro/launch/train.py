"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --steps 100 \
        --reduced --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``--reduced`` runs the smoke-scale config of the same family (CPU-sized);
without it the full assigned config is built (requires real accelerators).
``--cordic`` switches every matmul/AF onto the paper's FxP8 + DA-VINCI
execution policy.  ``--fault-at N`` injects a crash to exercise
checkpoint/restart (the supervisor restores and resumes).
"""
from __future__ import annotations

import argparse

from repro.configs import CORDIC_EXEC, get_arch
from repro.configs.base import LM_SHAPES
from repro.data.pipeline import stream_for_model
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime.train_loop import TrainConfig, Trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(LM_SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--cordic", action="store_true",
                    help="paper-faithful FxP8 + DA-VINCI execution")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--fault-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    shape = LM_SHAPES[args.shape]
    if args.batch or args.seq:
        import dataclasses
        shape = dataclasses.replace(
            shape, global_batch=args.batch or shape.global_batch,
            seq_len=args.seq or shape.seq_len)
    stream = stream_for_model(model, shape, seed=args.seed)
    tcfg = TrainConfig(
        optimizer=AdamWConfig(
            lr=args.lr, total_steps=args.steps,
            warmup_steps=max(args.steps // 20, 1),
            moment_dtype="int8" if args.int8_moments else "float32"),
        grad_accum=args.grad_accum,
        grad_compression=args.grad_compression,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    pol = CORDIC_EXEC if args.cordic else None
    trainer = Trainer(model, tcfg, stream, pol=pol)
    print(f"# {cfg.name}: {model.n_params():,} params "
          f"({model.n_active_params():,} active), exec="
          f"{(pol or cfg.exec_policy).tag()}")
    try:
        out = trainer.run(args.steps, seed=args.seed, fault_at=args.fault_at)
    except RuntimeError as e:
        if "injected fault" in str(e) and args.ckpt_dir:
            print(f"# fault: {e}; restarting from checkpoint")
            trainer = Trainer(model, tcfg, stream, pol=pol)
            out = trainer.run(args.steps, seed=args.seed)
        else:
            raise
    for step, loss in out["losses"]:
        print(f"step {step:5d}  loss {loss:.4f}")
    print(f"# wall {out['wall_s']:.1f}s  final loss {out['final_loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
