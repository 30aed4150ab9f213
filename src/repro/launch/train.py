"""End-to-end training driver.

Composes the full substrate: config registry -> synthetic data pipeline ->
sharded step (pjit) -> AdamW -> fault-tolerant loop (periodic async
checkpoints, restart-on-failure, straggler log) -> metrics.

On this CPU container it trains reduced configs end-to-end (examples/ uses
it for the ~100M-param run); on a real pod the same driver runs the full
configs — only --arch/--smoke and the mesh change (PIUMA's "the application
code does not need to change").

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt [--compress bf16]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from ..configs import get_config
from ..configs.common import (input_specs, make_step, state_shapes,
                              param_logical_axes, param_shardings)
from ..checkpoint.ckpt import CheckpointManager
from ..data import synthetic
from ..distributed.fault_tolerance import FTConfig, run_training
from ..distributed.sharding import make_rules
from ..models import transformer as TF
from ..models import gnn as GNN
from ..models import recsys as RS
from ..optim import adamw
from ..core.graph import uniform_random_graph


def build_batch_iter(ac, model_cfg, args):
    if ac.family == "lm":
        it = synthetic.lm_batches(args.batch, args.seq, model_cfg.vocab,
                                  seed=args.seed)
        return ({"tokens": jnp.asarray(b["tokens"])} for b in synthetic.prefetch(it))
    if ac.family == "recsys":
        it = synthetic.recsys_batches(args.batch, model_cfg.n_fields,
                                      model_cfg.rows_per_field, seed=args.seed)
        return ({k: jnp.asarray(v) for k, v in b.items()}
                for b in synthetic.prefetch(it))
    # gnn: resample a graph batch every step
    def gen():
        g = uniform_random_graph(args.gnn_nodes, 4, seed=args.seed)
        i = 0
        while True:
            b = synthetic.gnn_batch(model_cfg.arch, g, model_cfg.d_feat,
                                    model_cfg.n_classes,
                                    l_max=model_cfg.l_max, seed=args.seed + i)
            yield {k: jnp.asarray(v) for k, v in b.items()}
            i += 1
    return synthetic.prefetch(gen())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--gnn-nodes", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()

    ac = get_config(args.arch)
    model_cfg = ac.smoke if args.smoke else ac.model
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        mesh = jax.make_mesh((n_dev, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    rules = make_rules(mesh)

    # build a train bundle matching the runtime batch
    import dataclasses as dc
    from ..configs.common import SpecBundle
    bundle = SpecBundle("train", model_cfg, {}, {})
    opt = adamw.AdamWConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                            total_steps=args.steps,
                            moment_dtype=ac.moment_dtype)
    step = make_step(ac, bundle, rules, opt)

    key = jax.random.PRNGKey(args.seed)
    from ..configs.common import init_params as ip
    params = ip(ac, model_cfg, key)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"arch={args.arch} params={n_params/1e6:.1f}M devices={n_dev}")
    state = adamw.init_state_with_dtype(params, ac.moment_dtype)

    step_jit = jax.jit(step, donate_argnums=(0,))
    batches = build_batch_iter(ac, model_cfg, args)
    ckpt = CheckpointManager(args.ckpt_dir, every=args.ckpt_every, keep=3)

    logs = []

    def on_metrics(i, m):
        if i % args.log_every == 0 or i == args.steps:
            rec = {"step": i, **{k: float(np.asarray(v)) for k, v in m.items()}}
            logs.append(rec)
            print(json.dumps(rec), flush=True)

    t0 = time.time()
    state, report = run_training(step_jit, state, batches, ckpt, args.steps,
                                 FTConfig(ckpt_every=args.ckpt_every),
                                 on_metrics=on_metrics)
    dt = time.time() - t0
    print(f"done: {report['steps_run']} steps in {dt:.1f}s "
          f"({dt / max(report['steps_run'], 1):.3f}s/step), "
          f"restarts={report['restarts']}, "
          f"stragglers={len(report['straggler_events'])}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"logs": logs, "report": {k: v for k, v in report.items()
                                                if k != "straggler_events"}}, f)


if __name__ == "__main__":
    main()
