"""End-to-end driver: decentralized bilevel LM training with sharded
DAGM (the paper's technique at framework scale).

Eight agents (CPU devices emulate the mesh "data" axis) each hold a
*non-iid* shard of the synthetic token stream (heterogeneity-q domain
bias) and a local copy of the LM.  The bilevel problem is decentralized
loss-weight tuning:

    outer x ∈ R^{n_domains+1}: per-domain loss weights + log weight-decay
    inner y = LM parameters:   g_i = x-weighted CE on agent i's shard
                               + exp(x_wd)·||y||²/2
    outer f_i = unweighted CE on agent i's *validation* shard

All cross-agent traffic is lax.ppermute neighbor exchange (ring) —
vectors only, exactly Algorithm 2.  Defaults are CPU-sized (a few M
params, a few dozen rounds); scale flags up on real hardware (the same
script drives a pod via the production mesh).

    PYTHONPATH=src python examples/train_lm_dagm.py [--rounds 30]

`main(argv)` returns the loss history and ledger summary, so a caller
that already holds the devices (benchmarks/bench_comm.py) runs it in
its own process.
"""
import argparse
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import get_config
from repro.data import TokenDataConfig, make_token_batch
from repro.data.synthetic import agent_domain_bias
from repro.comm import parse_comm_spec
from repro.distributed.dagm_sharded import (make_sharded_dagm,
                                            sharded_comm_ledger)
from repro.solve import sharded_spec
from repro.models import build_model


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch-per-agent", type=int, default=2)
    ap.add_argument("--n-domains", type=int, default=8)
    ap.add_argument("--het-q", type=float, default=0.5)
    ap.add_argument("--mixing-dtype", default="f32",
                    choices=["f32", "bf16"],
                    help="gossip wire dtype (sharded_spec"
                         ".comm_dtype): bf16 halves ring traffic "
                         "(ROADMAP bf16-drift study)")
    ap.add_argument("--comm", default="identity",
                    help="repro.comm gossip spec (identity | bf16 | "
                         "int8[+ef] | int4[+ef] | top_k:<f>[+ef] | "
                         "rand_k:<f>[+ef]); generalizes --mixing-dtype")
    ap.add_argument("--json-out", default=None,
                    help="write the loss history + comm ledger summary "
                         "as JSON (benchmarks/bench_comm drift study)")
    args = ap.parse_args(argv)

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    print(f"[dagm-lm] {cfg.name}: {model.param_count()/1e6:.2f}M params "
          f"x {n} agents (ring, Metropolis)")

    D = args.n_domains

    # ---- bilevel objectives (per-agent; run inside shard_map) ----
    def weighted_ce(x, y, batch, weighted: bool):
        logits, _ = __import__("repro.models.transformer",
                               fromlist=["forward"]).forward(
            y, cfg, batch["tokens"])
        V = logits.shape[-1]
        lse = jax.nn.logsumexp(
            jnp.where(jnp.arange(V) >= cfg.vocab_size, -1e30,
                      logits.astype(jnp.float32)), axis=-1)
        true = jnp.take_along_axis(
            logits.astype(jnp.float32), batch["labels"][..., None],
            axis=-1)[..., 0]
        ce = lse - true                                # (B, S)
        if weighted:
            w = jax.nn.softmax(x[:D])[batch["domain"]]  # (B,)
            ce = ce * w[:, None] * D
        return jnp.mean(ce)

    def g_fn(x, y, batch):
        wd = 1e-5 * jnp.exp(jnp.clip(x[D], -3.0, 3.0))
        l2 = sum(jnp.sum(jnp.square(p)) for p in jax.tree.leaves(y))
        return weighted_ce(x, y, batch["train"], True) + 0.5 * wd * l2

    def f_fn(x, y, batch):
        return weighted_ce(x, y, batch["val"], False)

    dcfg = sharded_spec(alpha=0.3, beta=0.1, M=2, U=2, curvature=8.0,
                        comm_dtype=args.mixing_dtype, comm=args.comm)
    pol = parse_comm_spec(dcfg.comm.spec)
    step, w = make_sharded_dagm(g_fn, f_fn, dcfg, mesh)
    stochastic = pol.stochastic
    print(f"[dagm-lm] gossip: {pol.spec} "
          f"(mixing_dtype={args.mixing_dtype})")

    # ---- per-agent states + non-iid shards ----
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    y = jax.vmap(lambda k: model.init(k))(keys)       # (n, ...) stacked
    x = jnp.zeros((n, D + 1), jnp.float32)
    bias = agent_domain_bias(n, D, args.het_q)

    def shard_batch(step_idx, split):
        data_cfg = TokenDataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq_len,
            global_batch=args.batch_per_agent,
            n_domains=D, seed=split)
        per = [make_token_batch(data_cfg, step_idx * n + i,
                                domain_bias=bias[i]) for i in range(n)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        # domain id per sequence (approx: argmax of bias — labelling only)
        dom = jnp.tile(jnp.argmax(jnp.asarray(bias), -1)[:, None],
                       (1, args.batch_per_agent))
        stacked["domain"] = dom
        return stacked

    hist = []
    for k in range(args.rounds):
        batch = {"train": shard_batch(k, 0), "val": shard_batch(k, 1)}
        if stochastic:
            x, y, m = step(x, y, batch, jax.random.PRNGKey(1000 + k))
        else:
            x, y, m = step(x, y, batch)
        hist.append(float(m["outer_loss"]))
        if k % 5 == 0 or k == args.rounds - 1:
            print(f"[dagm-lm] round {k:3d} outer={hist[-1]:.4f} "
                  f"inner={float(m['inner_loss']):.4f} "
                  f"consensus_x={float(m['consensus_x']):.2e}")

    xbar = np.asarray(x).mean(0)
    print(f"[dagm-lm] learned domain weights: "
          f"{np.round(np.exp(xbar[:D]) / np.exp(xbar[:D]).sum(), 3)}")
    print(f"[dagm-lm] outer loss {hist[0]:.4f} -> {hist[-1]:.4f} "
          f"(improved={hist[-1] < hist[0]})")
    if not np.isfinite(hist[-1]):
        raise FloatingPointError(f"outer loss diverged: {hist[-1]}")
    local = jax.tree.map(lambda a: a[0], y)
    led = sharded_comm_ledger(dcfg, x[0], local, rounds=args.rounds)
    out = {"arch": cfg.name, "rounds": args.rounds, "comm": pol.spec,
           "mixing_dtype": args.mixing_dtype, "outer_loss": hist,
           "ledger": led.summary(args.rounds)}
    if args.json_out:
        import json
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"[dagm-lm] wrote {args.json_out}")
    print("OK")
    return out


if __name__ == "__main__":
    # eight agents: on the CPU the mesh's "data" axis is eight host
    # devices (read when the backend starts, so before any jax work)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
