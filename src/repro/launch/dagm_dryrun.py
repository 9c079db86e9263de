"""Production-mesh dry-run of the paper's own technique: one full DAGM
outer round (Algorithm 2 — M inner DGD steps, DIHGP, outer step) for the
decentralized bilevel loss-weight-tuning problem, with the inner variable
y = a full assigned-architecture LM, lowered + compiled on the 16×16
(or 2×16×16) mesh with no allocation.

Layout: agents = the "data" mesh axis (16 agents single-pod) or the
flattened ("pod", "data") product (32 agents multi-pod, two ring edges
crossing the pod boundary), on a Metropolis ring; tensor parallelism
over "model" *inside* each agent (shard_map auto axes).  All cross-agent
traffic is `lax.ppermute` of parameter-pytree vectors — the paper's
vector-communication pattern at pod scale.

    PYTHONPATH=src python -m repro.launch.dagm_dryrun --arch qwen3-4b \
        [--multi-pod] [--seq-len 4096] [--batch-per-agent 16] [--bf16-comm]

This is the §Perf "most representative of the paper's technique" lane:
the baseline is the paper-faithful f32 ring exchange; --bf16-comm and
--local-updates are the beyond-paper variants recorded separately in
EXPERIMENTS.md.
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.dagm_sharded import make_sharded_dagm
from repro.solve import sharded_spec
from repro.distributed.sharding import make_rules
from repro.launch.dryrun import collective_bytes_from_hlo
from repro.launch.mesh import make_production_mesh, roofline_terms
from repro.models import build_model

N_DOMAINS = 8


def build_dagm_bilevel(cfg, *, seq_len: int, batch_per_agent: int,
                       dcfg):
    """Per-agent bilevel objectives for decentralized loss-weight tuning
    (same formulation as examples/train_lm_dagm.py, dry-run sized)."""
    from repro.models import transformer as tf

    D = N_DOMAINS

    def weighted_ce(x, y, batch, weighted: bool):
        logits, _ = tf.forward(y, cfg, batch["tokens"], remat=True)
        V = logits.shape[-1]
        lse = jax.nn.logsumexp(
            jnp.where(jnp.arange(V) >= cfg.vocab_size, -1e30,
                      logits.astype(jnp.float32)), axis=-1)
        true = jnp.take_along_axis(
            logits.astype(jnp.float32), batch["labels"][..., None],
            axis=-1)[..., 0]
        ce = lse - true
        if weighted:
            wdom = jax.nn.softmax(x[:D])[batch["domain"]]
            ce = ce * wdom[:, None] * D
        return jnp.mean(ce)

    def g_fn(x, y, batch):
        wd = 1e-5 * jnp.exp(jnp.clip(x[D], -3.0, 3.0))
        l2 = sum(jnp.sum(jnp.square(p.astype(jnp.float32)))
                 for p in jax.tree.leaves(y))
        return weighted_ce(x, y, batch["train"], True) + 0.5 * wd * l2

    def f_fn(x, y, batch):
        return weighted_ce(x, y, batch["val"], False)

    return g_fn, f_fn


def batch_shapes(cfg, n_agents: int, seq_len: int, batch_per_agent: int):
    B, S = batch_per_agent, seq_len
    one = {"tokens": jax.ShapeDtypeStruct((n_agents, B, S), jnp.int32),
           "labels": jax.ShapeDtypeStruct((n_agents, B, S), jnp.int32),
           "domain": jax.ShapeDtypeStruct((n_agents, B), jnp.int32)}
    return {"train": one, "val": dict(one)}


def run(arch: str, *, multi_pod: bool = False, seq_len: int = 4096,
        batch_per_agent: int = 16, M: int = 2, U: int = 3,
        comm_dtype: str = "f32", param_dtype: str = "f32",
        mix_every: int = 1, verbose: bool = True) -> dict:
    COMPUTE_DTYPE = jnp.bfloat16 if param_dtype == "bf16" else jnp.float32
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_agents = axes.get("data", 1) * axes.get("pod", 1)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)

    # multi-pod: one 32-agent ring across ("pod", "data") — the ring is
    # laid out so consecutive agents are ICI neighbors and exactly two
    # edges cross the pod boundary (DESIGN.md §2)
    agent_axis = ("pod", "data") if multi_pod else "data"
    dcfg = sharded_spec(alpha=0.3, beta=0.1, M=M, U=U,
                        curvature=8.0, axis=agent_axis,
                        comm_dtype=comm_dtype, mix_every=mix_every,
                        unroll_loops=True)
    g_fn, f_fn = build_dagm_bilevel(cfg, seq_len=seq_len,
                                    batch_per_agent=batch_per_agent,
                                    dcfg=dcfg)

    model = build_model(cfg)
    rules = make_rules(cfg, mesh, fsdp=False)
    # params per agent: logical axes -> P with leading agent ("data") axis
    param_axes = model.param_axes()
    agent_ax0 = ("pod", "data") if multi_pod else "data"
    y_sharding = jax.tree.map(
        lambda ax_: NamedSharding(
            mesh, P(agent_ax0, *[rules.table.get(a) for a in ax_])),
        param_axes, is_leaf=lambda t: isinstance(t, tuple))
    y_spec = jax.tree.map(lambda s: P("data"), y_sharding)

    # Agents = the ring over the agent axis: 16 single-pod, 32 across
    # ("pod", "data") multi-pod.
    n_ring = axes["data"] * (axes.get("pod", 1) if multi_pod else 1)
    y_shape = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_ring,) + l.shape, COMPUTE_DTYPE),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                          COMPUTE_DTYPE)))
    x_shape = jax.ShapeDtypeStruct((n_ring, N_DOMAINS + 1), jnp.float32)
    bshape = batch_shapes(cfg, n_ring, seq_len, batch_per_agent)

    manual = {"pod", "data"} if multi_pod else {"data"}
    step, _ = make_sharded_dagm(g_fn, f_fn, dcfg, mesh,
                                manual_axes=manual, jit_step=False)

    x_sh = NamedSharding(mesh, P(agent_axis))
    b_sh = jax.tree.map(lambda _: NamedSharding(mesh, P(agent_axis)),
                        bshape)

    t0 = time.time()
    # NOTE: rules are used only to build the boundary in_shardings; the
    # model's internal shard() constraints must stay OFF inside the
    # shard_map manual region (their NamedShardings carry the fully-Auto
    # mesh and clash with the Manual context) — GSPMD propagates the
    # model-axis layout from the parameter shardings instead.
    lowered = jax.jit(step,
                      in_shardings=(x_sh, y_sharding, b_sh),
                      donate_argnums=(0, 1)).lower(
        x_shape, y_shape, bshape)
    compiled = lowered.compile()
    compile_s = time.time() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    peak = float(getattr(mem, "temp_size_in_bytes", 0)
                 + getattr(mem, "argument_size_in_bytes", 0)
                 + getattr(mem, "output_size_in_bytes", 0)
                 - getattr(mem, "alias_size_in_bytes", 0))
    terms = roofline_terms(flops, byts, sum(coll.values()))
    bound = terms.pop("bottleneck")
    out = {"arch": arch, "mesh": mesh_name, "M": M, "U": U,
           "comm_dtype": comm_dtype, "param_dtype": param_dtype,
           "mix_every": mix_every, "seq_len": seq_len,
           "batch_per_agent": batch_per_agent,
           "compile_s": compile_s, "flops": flops, "bytes": byts,
           "peak_memory_per_device": peak,
           "collective_bytes": coll, "roofline": terms,
           "bottleneck": bound}
    if verbose:
        t = {k: f"{v*1e3:.2f}ms" for k, v in terms.items()}
        print(f"[dagm-dryrun] OK {arch} ({mesh_name}) M={M} U={U} "
              f"comm={comm_dtype} compile={compile_s:.1f}s "
              f"mem/dev={peak/1e9:.2f}GB "
              f"coll={sum(coll.values())/1e9:.3f}GB roofline={t} "
              f"bound={bound}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--batch-per-agent", type=int, default=16)
    ap.add_argument("--inner-steps", type=int, default=2)
    ap.add_argument("--neumann-u", type=int, default=3)
    ap.add_argument("--comm-dtype", default="f32",
                    choices=["f32", "bf16"])
    ap.add_argument("--param-dtype", default="f32",
                    choices=["f32", "bf16"])
    ap.add_argument("--mix-every", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    res = run(args.arch, multi_pod=args.multi_pod, seq_len=args.seq_len,
              batch_per_agent=args.batch_per_agent, M=args.inner_steps,
              U=args.neumann_u, comm_dtype=args.comm_dtype,
              param_dtype=args.param_dtype, mix_every=args.mix_every)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
