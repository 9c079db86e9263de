"""Decentralized mixing as TPU collectives.

The paper's gossip step — each agent averages its state with its graph
neighbors through the mixing matrix W — maps onto `lax.ppermute` for
circulant (shift-invariant) graphs: W·y at agent i is a weighted sum of
y from agents i±o for the offsets o of the graph.  ppermute is the
native contention-free ICI pattern, and *no all-reduce appears anywhere
in the optimization path* (the paper's communication-efficiency claim,
made structural).

Works on arbitrary pytrees (model-parameter states).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.topology import make_network, Network


@dataclasses.dataclass(frozen=True)
class RingWeights:
    """Shift-invariant mixing weights: w_self + {offset: weight}."""
    n: int
    w_self: float
    offsets: dict  # offset (±o) -> weight

    @classmethod
    def metropolis_ring(cls, n: int) -> "RingWeights":
        # ring: deg 2 everywhere -> w_edge = 1/3, w_self = 1/3
        return cls(n=n, w_self=1.0 / 3.0,
                   offsets={+1: 1.0 / 3.0, -1: 1.0 / 3.0})

    @classmethod
    def metropolis_circulant(cls, n: int, hops: int) -> "RingWeights":
        """2·hops-regular circulant with Metropolis weights."""
        deg = 2 * hops
        w = 1.0 / (1.0 + deg)
        offs = {}
        for o in range(1, hops + 1):
            offs[+o] = w
            offs[-o] = w
        return cls(n=n, w_self=1.0 - deg * w, offsets=offs)

    def to_network(self) -> Network:
        """Dense-W Network equivalent (reference-tier comparisons)."""
        hops = max(abs(o) for o in self.offsets)
        return make_network("circulant", self.n,
                            offsets=tuple(range(1, hops + 1)))


def ppermute_shift(x, axis_name: str, offset: int, n: int):
    """Receive the value held by agent (i - offset) mod n."""
    perm = [(j, (j + offset) % n) for j in range(n)]
    return lax.ppermute(x, axis_name, perm)


def ring_mix(tree, axis_name: str, w: RingWeights, comm_dtype=None):
    """(W ⊗ I) applied to per-agent pytree state via neighbor exchange.

    `comm_dtype` (e.g. jnp.bfloat16) quantizes only the *communicated*
    copies; the local term and the accumulation stay in the leaf dtype.
    This is the beyond-paper compressed-gossip variant (EXPERIMENTS
    §Perf) — cf. Koloskova et al. [34] on compressed decentralized SGD.
    """
    def mix_leaf(x):
        out = w.w_self * x
        if comm_dtype is None:
            send = x
        else:
            # optimization_barrier pins the down-cast *before* the
            # ppermute: XLA otherwise commutes convert past the permute
            # (elementwise ∘ data-movement) and the wire stays f32 —
            # measured in EXPERIMENTS §Perf-3.
            send = lax.optimization_barrier(x.astype(comm_dtype))
        for offset, weight in w.offsets.items():
            recv = ppermute_shift(send, axis_name, offset, w.n)
            out = out + weight * recv.astype(x.dtype)
        return out
    with jax.named_scope("gossip"):
        return jax.tree.map(mix_leaf, tree)


def ring_laplacian(tree, axis_name: str, w: RingWeights, comm_dtype=None):
    """((I − W) ⊗ I) x."""
    mixed = ring_mix(tree, axis_name, w, comm_dtype)
    return jax.tree.map(lambda a, b: a - b, tree, mixed)


# ---- compressed gossip channel (repro.comm) ----

def ring_mix_c(tree, axis_name: str, w: RingWeights, policy, st):
    """`ring_mix` through a `repro.comm` channel -> (mixed, state).

    Each agent transmits the compressed payload of its pytree state —
    with CHOCO-style error feedback the innovation against the replica
    `st.hat` its neighbors hold — while the self-weight term w_self·x
    stays exact (it never crosses the wire).  "identity" delegates to
    the plain path bit-for-bit; "bf16" keeps the optimization_barrier
    down-cast so the wire really is 2 bytes/float; value-simulated
    compressors (int8/int4/top_k/rand_k) quantize the payload values
    before the ppermute — the packed wire is the ROADMAP fused
    quantize+gather Pallas kernel.  `st` is a `ChannelState` whose
    `hat` mirrors the tree structure (see `sharded_channel_init`)."""
    with jax.named_scope("gossip"):
        return _ring_mix_c(tree, axis_name, w, policy, st)


def _ring_mix_c(tree, axis_name: str, w: RingWeights, policy, st):
    from repro.comm import compressed_payload_local
    if policy.is_identity:
        return ring_mix(tree, axis_name, w), st.bump()
    if policy.compressor.name == "bf16" and not policy.ef:
        return ring_mix(tree, axis_name, w, jnp.bfloat16), st.bump()

    leaves, treedef = jax.tree.flatten(tree)
    if policy.stochastic:
        key, *subs = jax.random.split(st.key, len(leaves) + 1)
    else:
        key, subs = st.key, [None] * len(leaves)
    hats = treedef.flatten_up_to(st.hat) if policy.ef \
        else [None] * len(leaves)
    payloads, new_hats = [], []
    for leaf, hat, sub in zip(leaves, hats, subs):
        p, h = compressed_payload_local(policy, leaf, hat, sub)
        payloads.append(p)
        new_hats.append(h)

    def mix_leaf(x, xh):
        out = w.w_self * x
        send = lax.optimization_barrier(xh)
        for offset, weight in w.offsets.items():
            out = out + weight * ppermute_shift(send, axis_name, offset,
                                                w.n)
        return out
    mixed = treedef.unflatten([mix_leaf(x, xh) for x, xh
                               in zip(leaves, payloads)])
    hat = treedef.unflatten(new_hats) if policy.ef else st.hat
    return mixed, dataclasses.replace(st, hat=hat, key=key,
                                      sends=st.sends + 1)


def ring_laplacian_c(tree, axis_name: str, w: RingWeights, policy, st):
    """((I − W) ⊗ I) x through the compressed channel."""
    mixed, st = ring_mix_c(tree, axis_name, w, policy, st)
    return jax.tree.map(lambda a, b: a - b, tree, mixed), st


# ---- pytree vector-space helpers used by the sharded DAGM ----

def tadd(a, b):
    return jax.tree.map(jnp.add, a, b)


def tsub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tscale(c, a):
    return jax.tree.map(lambda x: c * x, a)


def taxpy(c, a, b):
    """b + c * a."""
    return jax.tree.map(lambda x, y: y + c * x, a, b)


def tdot(a, b):
    return sum(jnp.vdot(x, y) for x, y
               in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def tnorm(a):
    return jnp.sqrt(tdot(a, a).real)
