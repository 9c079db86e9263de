"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b \
        --smoke --steps 20 --seq-len 128 --global-batch 8

Builds the mesh over available devices, applies the sharding rules,
streams the synthetic token pipeline, runs the jitted train step with
checkpointing and logging.  `--smoke` swaps in the reduced config so the
same driver runs on CPU; on a real TPU slice drop `--smoke` and point
`--mesh` at the slice shape.  `--dagm` switches the optimizer from
AdamW data-parallelism to the paper's decentralized bilevel trainer
(see examples/train_lm_dagm.py for the bilevel formulation).
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore_checkpoint, \
    save_checkpoint
from repro.configs import get_config
from repro.data import TokenDataConfig, make_token_batch
from repro.launch.mesh import make_host_mesh
from repro.distributed.sharding import make_rules, tree_param_sharding, \
    use_rules
from repro.models import build_model
from repro.models.steps import make_train_step
from repro.optim import adamw, cosine_schedule
from jax.sharding import NamedSharding


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)

    mesh = make_host_mesh(model=args.model_parallel)
    rules = make_rules(cfg, mesh)
    print(f"[train] {cfg.name}: {model.param_count()/1e6:.1f}M params, "
          f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    opt = adamw(cosine_schedule(args.lr, args.warmup, args.steps))
    data_cfg = TokenDataConfig(vocab_size=cfg.vocab_size,
                               seq_len=args.seq_len,
                               global_batch=args.global_batch,
                               seed=args.seed)

    with use_rules(rules):
        params = model.init(jax.random.PRNGKey(args.seed))
        opt_state = opt.init(params)
        param_sh = tree_param_sharding(model.param_axes(), rules)
        params = jax.device_put(params, param_sh)
        step_fn = jax.jit(make_train_step(
            model, opt, microbatches=args.microbatches))

        start = 0
        if args.ckpt_dir and (s := latest_step(args.ckpt_dir)) is not None:
            params = restore_checkpoint(args.ckpt_dir, s, params)
            start = s
            print(f"[train] restored step {s}")

        losses = []
        t0 = time.time()
        for step in range(start, args.steps):
            batch = make_token_batch(data_cfg, step)
            if cfg.encoder_decoder:
                batch["frames"] = 0.02 * jax.random.normal(
                    jax.random.PRNGKey(step),
                    (args.global_batch, cfg.encoder_frames, cfg.d_model))
            batch = {k: jax.device_put(
                v, NamedSharding(mesh, rules.resolve(
                    "batch", *([None] * (v.ndim - 1)))))
                for k, v in batch.items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                      f"({dt:.1f}s)")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, step + 1, params)
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.steps, params)
        improved = losses[-1] < losses[0]
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"(improved={improved})")
        return 0 if np.isfinite(losses[-1]) else 1


if __name__ == "__main__":
    import sys
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
