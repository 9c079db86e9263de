"""Mixture-of-Experts layer: top-k token-choice routing, capacity-bounded
sort/gather dispatch (MegaBlocks/MaxText style — avoids the O(T²)
GShard one-hot einsum), SwiGLU experts, load-balance auxiliary loss.

Default layout is tensor-parallel *inside* each expert (d_ff over the
"model" mesh axis, expert count replicated); expert-parallel layout
("experts" → "model") is selected via sharding rules (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from .layers import Maker, Params


def init_moe(mk: Maker, cfg) -> Params:
    d, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": mk((d, E), (None, "experts"), scale=0.02),
        "wg": mk((E, d, F), ("experts", "fsdp", "ffn")),
        "wu": mk((E, d, F), ("experts", "fsdp", "ffn")),
        "wd": mk((E, F, d), ("experts", "ffn", "fsdp")),
    }


def expert_capacity(T: int, E: int, k: int, factor: float) -> int:
    c = int(T * k * factor / E) + 1
    return max(4, -(-c // 4) * 4)          # round up to a multiple of 4


def moe(p: Params, x, cfg):
    """Returns (out, aux_loss).  x: (B, S, D).

    With cfg.moe_route_groups = G > 1 the tokens are split into G groups
    (grouped on the batch axis, which is data-sharded), each routed and
    dispatched independently: the routing sort and the (E, C, D) dispatch
    buffers then carry a leading group axis sharded over "batch", instead
    of one global sort + replicated buffers.  Routing decisions are
    identical (router is per-token); only capacity is enforced per group,
    which is the standard EP/DP-local semantics (GShard/MaxText)."""
    B, S, D = x.shape
    G = max(cfg.moe_route_groups, 1)
    if G > 1:
        impl = _moe_grouped_shard_map if cfg.moe_group_impl == "shard_map" \
            else _moe_grouped
        out, aux = impl(p, x, cfg)
        if out is not None:
            return out, aux
    out, aux = _moe_dispatch(p, x.reshape(B * S, D), cfg)
    return out.reshape(B, S, D), aux


def _moe_grouped_shard_map(p: Params, x, cfg):
    """Grouped dispatch as an explicit shard_map over the batch mesh
    axes — the partitioner cannot insert cross-shard traffic at all
    (each shard routes and dispatches its own tokens; expert weights
    stay on the auto "model" axis).

    Differentiation: XLA's SPMD partitioner check-fails when asked to
    *transpose* this shard_map at 512 host devices (EXPERIMENTS
    §Perf-1), so the VJP is supplied explicitly — forward and backward
    are each their own plain (never-transposed) shard_map; the backward
    recomputes the local dispatch (remat-style residuals = (p, x)) and
    psums the parameter cotangents over the batch axes.

    Falls back to the batched formulation when no mesh rules are
    installed."""
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.distributed.sharding import current_rules

    rules = current_rules()
    if rules is None:
        return _moe_grouped(p, x, cfg)
    batch_axes = rules.table.get("batch")
    if not batch_axes:
        return None, None
    ax = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
    sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))
    dp = int(np.prod([sizes[a] for a in ax]))
    B, S, D = x.shape
    if B % dp:
        return None, None
    mesh = rules.mesh
    pspecs = jax.tree.map(lambda _: P(), p)

    # Re-lay fsdp-sharded ("data"-axis) parameter leaves OUTSIDE the
    # manual region: asking the partitioner to do that re-layout at the
    # shard_map boundary is what check-fails on the CPU backend (it is
    # also where the FSDP all-gather belongs — explicit and hoistable).
    from jax.sharding import NamedSharding

    def _no_batch(logical):
        m = rules.table.get(logical) if logical is not None else None
        mt = m if isinstance(m, tuple) else (m,)
        return None if set(mt) & set(ax) else m

    def degather(axes, leaf):
        spec = P(*[_no_batch(a) for a in axes])
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    from repro.models.layers import Maker
    ax_tree = init_moe(Maker(None), cfg)
    p = jax.tree.map(degather, ax_tree, p,
                     is_leaf=lambda t: isinstance(t, tuple))

    def local_fwd(xl, pl):
        o, a = _moe_dispatch(pl, xl.reshape(-1, D), cfg)
        return o.reshape(xl.shape), jax.lax.pmean(a, ax)

    fwd_sm = shard_map(local_fwd, mesh=mesh, in_specs=(P(ax), pspecs),
                       out_specs=(P(ax), P()),
                       axis_names=frozenset(ax), check_vma=False)

    @jax.custom_vjp
    def run(pp, xx):
        return fwd_sm(xx, pp)

    def run_fwd(pp, xx):
        return fwd_sm(xx, pp), (pp, xx)

    def run_bwd(res, ct):
        pp, xx = res
        ct_o, ct_a = ct

        def local_bwd(xl, pl, cto, cta):
            def f(pl_, xl_):
                o, a = _moe_dispatch(pl_, xl_.reshape(-1, D), cfg)
                return o.reshape(xl.shape), a
            _, vjp = jax.vjp(f, pl, xl)
            # aux was pmean'd over dp shards ⇒ local cotangent cta/dp
            dpl, dxl = vjp((cto, cta / dp))
            # per-shard contribution with a leading shard axis; the sum
            # over shards happens OUTSIDE the manual region (a psum of
            # auto-model-sharded cotangents inside shard_map is the op
            # that check-fails the CPU partitioner)
            return jax.tree.map(lambda t: t[None], dpl), dxl

        dpspecs = jax.tree.map(lambda _: P(ax), pspecs)
        bwd_sm = shard_map(local_bwd, mesh=mesh,
                           in_specs=(P(ax), pspecs, P(ax), P()),
                           out_specs=(dpspecs, P(ax)),
                           axis_names=frozenset(ax), check_vma=False)
        dpp, dxx = bwd_sm(xx, pp, ct_o, ct_a)
        return jax.tree.map(lambda t: t.sum(0), dpp), dxx

    run.defvjp(run_fwd, run_bwd)
    return run(p, x)


def _moe_grouped(p: Params, x, cfg):
    """Group-local dispatch: G independent routing domains, the group
    axis sharded over the batch mesh axes.

    Written as *batched* sort/scatter/gather with the group axis leading
    and sharding constraints on every major intermediate, so the
    partitioner keeps each group's sort and (E, C, D) dispatch buffers
    on its own data shard.  (A shard_map formulation is semantically
    cleaner but trips an XLA check-failure under grad+scan on this
    backend; a vmap + constraint formulation loses the group sharding
    through the batching rule and re-replicates.  Both measured —
    EXPERIMENTS.md §Perf-1.)
    """
    B, S, D = x.shape
    G = cfg.moe_route_groups
    if B % G:
        return None, None
    E, k = cfg.num_experts, cfg.top_k
    T = (B // G) * S
    C = expert_capacity(T, E, k, cfg.capacity_factor)

    xg = shard(x.reshape(G, T, D), "batch", None, None)
    logits = jnp.einsum("gtd,de->gte", xg,
                        p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                   # (G, T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=1)                           # (G, E)
    ce = jnp.mean(jax.nn.one_hot(eidx[..., 0], E), axis=1)
    aux = E * jnp.mean(jnp.sum(me * ce, axis=-1))

    # ---- per-group sort/gather dispatch ----
    gi = jnp.arange(G)[:, None]                            # group index
    flat_e = eidx.reshape(G, T * k)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(T), k)[None], (G, T * k))
    flat_g = gate.reshape(G, T * k)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    se = jnp.take_along_axis(flat_e, order, 1)
    st = jnp.take_along_axis(flat_t, order, 1)
    sg = jnp.take_along_axis(flat_g, order, 1)
    counts = jnp.zeros((G, E), se.dtype).at[gi, se].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((G, 1), counts.dtype),
         jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    slot = jnp.arange(T * k)[None] - jnp.take_along_axis(starts, se, 1)
    keep = slot < C
    dest = jnp.where(keep, se * C + slot, E * C)           # OOB → dropped

    # integer-array gather, NOT take_along_axis: the latter broadcasts
    # its index tensor to (G, T·k, D) u32 — 51.5 GB that XLA then
    # all-gathers (EXPERIMENTS §Perf-1 iter 4).
    gathered = shard(xg[gi, st], "batch", None, None)      # (G, T·k, D)
    # constrain the scatter *operand* too — an unconstrained zeros
    # operand makes GSPMD replicate the whole scatter (measured:
    # ~36 GB/layer of gratuitous all-gather; EXPERIMENTS §Perf-1 iter 3)
    base = shard(jnp.zeros((G, E * C, D), x.dtype), "batch", None, None)
    buf = base.at[gi, dest].set(gathered, mode="drop")
    buf = shard(buf.reshape(G, E, C, D), "batch", "experts", None, None)

    a = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["wg"])) \
        * jnp.einsum("gecd,edf->gecf", buf, p["wu"])
    a = shard(a, "batch", "experts", None, "ffn")
    out_buf = jnp.einsum("gecf,efd->gecd", a, p["wd"])
    out_flat = out_buf.reshape(G, E * C, D)

    contrib = jnp.where(
        keep[..., None],
        out_flat[gi, jnp.minimum(dest, E * C - 1)]
        * sg[..., None].astype(x.dtype), 0.0)
    contrib = shard(contrib, "batch", None, None)
    out_base = shard(jnp.zeros((G, T, D), x.dtype), "batch", None, None)
    out = out_base.at[gi, st].add(contrib)
    out = shard(out, "batch", None, None)
    return out.reshape(B, S, D), aux


def _moe_dispatch(p: Params, xt, cfg):
    """Single routing domain: xt (T, D) -> (out (T, D), aux scalar)."""
    T, D = xt.shape
    E, k = cfg.num_experts, cfg.top_k
    C = expert_capacity(T, E, k, cfg.capacity_factor)

    logits = (xt @ p["router"]).astype(jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                     # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary loss (Switch-style) ----
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], E), axis=0)
    aux = E * jnp.sum(me * ce)

    # ---- sort/gather dispatch ----
    flat_e = eidx.reshape(-1)                                # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # rank within expert group = position - group start
    counts = jnp.bincount(se, length=E)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(T * k) - starts[se]
    keep = slot < C                                          # drop overflow
    dest = jnp.where(keep, se * C + slot, E * C)             # OOB → dropped

    buf = jnp.zeros((E * C, D), xt.dtype).at[dest].set(
        xt[st], mode="drop")
    buf = buf.reshape(E, C, D)
    buf = shard(buf, "experts", None, None)

    def ffn(wg, wu, wd, h):
        a = jax.nn.silu(h @ wg) * (h @ wu)
        a = shard(a, None, "ffn")
        return a @ wd

    out_buf = jax.vmap(ffn)(p["wg"], p["wu"], p["wd"], buf)  # (E, C, D)
    out_flat = out_buf.reshape(E * C, D)
    contrib = jnp.where(keep[:, None], out_flat[jnp.minimum(dest, E * C - 1)]
                        * sg[:, None].astype(xt.dtype), 0.0)
    out = jnp.zeros((T, D), xt.dtype).at[st].add(contrib)
    return out, aux
