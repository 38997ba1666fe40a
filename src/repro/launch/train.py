"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

By default it trains the *reduced* config of the arch end-to-end; ``--full``
builds the published widths — the mesh adapts to ``jax.device_count()``.
The persistent compilation cache follows ``launch/compile_cache.py``.

Demonstrates the full production loop: sharded init, synthetic data
pipeline with prefetch, the selected gradient-reduction schedule (C4),
checkpoint-restart, straggler monitoring.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs.base import ShapeConfig
from repro.data import make_pipeline
from repro.data.pipeline import family_extras_fn
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import registry
from repro.runtime import Trainer, TrainConfig


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=list(registry.ARCH_NAMES))
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--full", action="store_true",
                   help="build the arch at its published widths (default: "
                        "the reduced smoke-test config)")
    p.add_argument("--reduction", default="gspmd",
                   choices=["gspmd", "hier", "hier_tree", "hier_ef8"])
    p.add_argument("--remat", default="full",
                   choices=["none", "full", "dots", "save_tp"])
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--data-axis", type=int, default=None,
                   help="data-axis size (default: all devices)")
    p.add_argument("--model-axis", type=int, default=1)
    args = p.parse_args(argv)

    enable_compile_cache()
    ndev = jax.device_count()
    data = args.data_axis or (ndev // args.model_axis)
    mesh = make_test_mesh((data, args.model_axis), ("data", "model"))
    print(f"mesh: data={data} model={args.model_axis} ({ndev} devices)")

    bundle = registry.build(args.arch, reduced=not args.full)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    tcfg = TrainConfig(
        num_steps=args.steps, reduction=args.reduction, remat=args.remat,
        microbatches=args.microbatches, peak_lr=args.lr,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        log_every=args.log_every)
    trainer = Trainer(bundle.model, mesh, tcfg)
    state, start = trainer.maybe_restore()
    print(f"starting at step {start}")
    pipe = make_pipeline(
        bundle.cfg, shape, start_step=start,
        num_steps=args.steps - start,
        sharding=trainer.shardings["batch"],
        extras_fn=family_extras_fn(bundle.cfg))
    state = trainer.run(pipe, start_step=start, state=state)
    hist = state["_history"]
    print(f"done: {len(hist)} log records; "
          f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    if trainer.monitor.events:
        print(f"straggler events: {trainer.monitor.events}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
