"""`Network` + the topology-aware `MixingOp` execution backend.

W itself is small (n × n with n = number of agents) and always
materialized; what is *hot* is applying W ⊗ I to stacked per-agent
states (n, d) — called M + U + 1 times per DAGM outer round.  The paper's
communication-efficiency claim rests on this being a neighbor-only
operation (O(n·k·d) for k neighbors per agent), so the runtime must not
lower it through a dense O(n²·d) matmul on sparse topologies.

MixingOp backends
-----------------
`MixingOp` (built from a `Network` via `make_mixing_op`) owns that
dispatch.  Backends:

  * "dense"               — W @ y matmul; correct for arbitrary W (the
                            complete-graph / near-dense fallback).
  * "circulant"           — for shift-invariant W (ring, 2k-regular
                            circulant; detected by `circulant_structure`):
                            O(n·k·d) weighted cyclic shifts in plain XLA.
  * "circulant_pallas"    — same math via the banded-circulant Pallas
                            kernels in `repro.kernels.mixing_matvec`
                            (single-read column-stripe tiling, f32/bf16);
                            non-tile-multiple shapes fall back to dense.
  * "sparse_gather"       — for *irregular* sparse W (Erdős–Rényi, star;
                            extracted by `sparse_structure`): plain-XLA
                            take-based gather, O((nnz+n)·d) — a padded
                            per-slot row-gather loop on near-regular
                            degree distributions, CSR take/segment-sum
                            on skewed ones (see kernels.ref).
  * "sparse_gather_pallas"— the per-row neighbor-gather Pallas kernel
                            (scalar-prefetched index/weight tables,
                            column-stripe grid), O(n·k_max·d); non-tile-
                            multiple shapes fall back to "sparse_gather".
  * "auto"                — circulant when shift-invariant *and* cheaper
                            than the matmul (2·(k+1) ≤ n); else
                            sparse_gather when the gather does strictly
                            fewer MACs than the matmul (nnz + n < n², i.e.
                            anything but a complete graph); else dense.
                            Upgrades to the matching Pallas tier when
                            `repro.kernels.ops.use_pallas(True)` is set.

The sharded runtime is a further tier of the same abstraction: on a real
mesh W·y is `lax.ppermute` neighbor exchange (repro.distributed
.collectives.ring_mix), one agent per device, and never sees a dense W.

Mixing dtype
------------
`MixingOp(..., dtype="bf16")` stores/communicates the mixed state in
bfloat16 while accumulating in f32 (ROADMAP bf16 item): the operand is
rounded to bf16 once, every backend accumulates the rounded values in
f32, and the result is rounded back through bf16 before being returned
in the caller's dtype.  `resolve_mixing_dtype` is the single vocabulary
("f32" | "bf16") shared with the sharded tier's
`ShardedDAGMConfig.comm_dtype` compressed gossip.

Compressed gossip (`repro.comm`)
--------------------------------
`MixingOp(..., comm="int8+ef")` generalizes the dtype knob into the
full compressed-gossip subsystem: the op carries a parsed
`repro.comm.CommPolicy` plus a `CommLedger`, and the `*_c` variants
(`mix_c` / `laplacian_c` / `neumann_step_c`, façades `mix_apply_c` /
`laplacian_apply_c` / `fused_neumann_step_c`) apply
compress→mix→decompress around every gossip: the payload the neighbors
receive is the compressor roundtrip (with CHOCO-style error feedback
when the spec says `+ef`), the backend mixes the decoded payload, and
the self-weight term w_ii·y_i — which never crosses the wire — is
re-applied exactly.  Each `comm_channel` registers its payload shape in
the ledger; the `ChannelState` threaded through the caller's scan
counts sends, so the post-run ledger reports exact wire bytes from the
actual compressor calls.  `comm="identity"` short-circuits every `*_c`
call onto the uncompressed code path (bit-identical trajectories, only
the counters tick).

When the policy is a *fusable* quantizer (int8/int4, ± EF) and the
Pallas tier is active, the `*_c` calls run the comm-fused kernels
instead: one VMEM traversal performs compress→mix→decompress (and, on
the full-stripe circulant tier without EF, the whole Neumann update) —
same `row_quant_params` wire metadata, same ChannelState advance, same
payload-byte accounting; only the stochastic-rounding uniforms come
from the in-kernel counter PRNG instead of `jax.random.uniform`
(statistically equivalent by the quantizer's unbiasedness).  Identity /
bf16 / top-k / rand-k policies, bf16 storage, masked views and
non-tileable shapes keep today's XLA compose path bitwise-identically.
Oversized agent counts (full stripe past the kernels' VMEM budget)
switch to the row-tiled halo kernels automatically — `_stripe_plan` /
`pick_halo_bn` — and every impossible-tier case falls back silently
with a one-time RuntimeWarning naming the shape.

Fault-masked mixing (`repro.faults`)
------------------------------------
`MixingOp.masked(mask)` returns a `MaskedMixingOp` view applying this
round's realized matrix W_k = W ⊙ M (off-diagonal) with every dropped
link's weight folded back into the self-weight — so W_k stays symmetric
and doubly stochastic for symmetric masks (degradation, not
divergence).  The mask lives in the padded neighbor-table layout of
`sparse_structure` ((n, k_max) float, 1 = link alive) and is an
ordinary traced operand: scanning per-round masks through
`core.dagm.dagm_run_chunk` replays any fault trace through ONE compiled
program, zero retraces.  The masked view always executes the padded
row-gather formulation (a mask breaks the shift invariance the
circulant/Pallas tiers exploit), reusing `kernels.ref
.sparse_mix_padded_ref` with effective tables — an all-ones mask is
therefore bit-exact with the fault-free "sparse_gather" padded path.
`mix_masked` / `laplacian_masked` are one-shot conveniences over the
view.

All algorithm-level callers (`penalty`, `dihgp`, `dagm`, `baselines`)
go through the free functions `mix_apply` / `laplacian_apply` /
`fused_neumann_step` (or their `_c` twins), which accept either a raw W
array (dense path, backward compatible) or a `MixingOp` — so a single
`DAGMConfig.mixing` / `DAGMConfig.comm` choice selects the execution
path end-to-end with no call-site branching.
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .graphs import (circulant_graph, complete_graph, erdos_renyi_graph,
                     is_connected, ring_graph, star_graph)
from .structure import (CirculantStructure, SparseStructure,
                        circulant_structure, sparse_structure)
from .weights import (check_assumption_a, max_degree_weights,
                      metropolis_weights, mixing_rate, self_weight_bounds,
                      uniform_averaging)


# ---------------------------------------------------------------------------
# Topology bundle
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Network:
    """A validated decentralized network: adjacency + mixing matrix."""
    adj: np.ndarray
    W: np.ndarray
    name: str = "network"

    @property
    def n(self) -> int:
        return self.W.shape[0]

    @property
    def sigma(self) -> float:
        return mixing_rate(self.W)

    @property
    def theta_bounds(self) -> tuple[float, float]:
        return self_weight_bounds(self.W)

    def neighbors(self, i: int) -> np.ndarray:
        return np.nonzero(self.adj[i])[0]

    def W_jnp(self, dtype=jnp.float32) -> jnp.ndarray:
        return jnp.asarray(self.W, dtype=dtype)

    @property
    def num_edges(self) -> int:
        return int(self.adj.sum()) // 2

    def content_key(self) -> tuple:
        """Hashable identity by content: name, n and a digest of W.
        Two Networks with equal (name, n) but different W get different
        keys, so a program traced for one never runs on the other."""
        digest = hashlib.sha1(
            np.ascontiguousarray(self.W).tobytes()).hexdigest()
        return ("net", self.name, self.n, digest)


def make_network(kind: str, n: int, *, weights: str = "metropolis",
                 r: float = 0.5, offsets: Sequence[int] = (1,),
                 seed: int = 0) -> Network:
    """Factory: kind in {ring, circulant, erdos_renyi, complete, star,
    uniform}; weights in {metropolis, max_degree}."""
    if kind == "ring":
        adj = ring_graph(n)
    elif kind == "circulant":
        adj = circulant_graph(n, offsets)
    elif kind == "erdos_renyi":
        adj = erdos_renyi_graph(n, r, seed)
    elif kind == "complete":
        adj = complete_graph(n)
    elif kind == "star":
        adj = star_graph(n)
    elif kind == "uniform":
        adj = complete_graph(n)
        W = uniform_averaging(n)
        check_assumption_a(W, adj)
        return Network(adj=adj, W=W, name=f"uniform-{n}")
    else:
        raise ValueError(f"unknown graph kind {kind!r}")
    if not is_connected(adj):
        raise ValueError(f"{kind} graph with n={n} is not connected")
    if weights == "metropolis":
        W = metropolis_weights(adj)
    elif weights == "max_degree":
        W = max_degree_weights(adj)
    else:
        raise ValueError(f"unknown weight scheme {weights!r}")
    check_assumption_a(W, adj)
    return Network(adj=adj, W=W, name=f"{kind}-{weights}-{n}")


# ---------------------------------------------------------------------------
# MixingOp backend
# ---------------------------------------------------------------------------

BACKENDS = ("auto", "dense", "circulant", "circulant_pallas",
            "sparse_gather", "sparse_gather_pallas")

MIXING_DTYPES = ("f32", "bf16")

# one warning per (op name, kind, detail) — Pallas fallbacks must never
# raise out of a jitted hot loop, but the user should learn once why a
# requested tier is not running
_FALLBACK_WARNED: set = set()


def _warn_pallas_fallback(name: str, kind: str, detail: str) -> None:
    # the warning fires once, but the labeled obs counter ticks on
    # EVERY fallback dispatch — long-running serve processes keep the
    # degradation visible in metric snapshots after the warning is gone
    from repro.obs import fused_fallback_counter
    fused_fallback_counter().labels(op=name, kind=kind).inc()
    key = (name, kind, detail)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    warnings.warn(
        f"MixingOp({name}): {kind} falling back to the XLA path — "
        f"{detail} (warned once per op/shape)", RuntimeWarning,
        stacklevel=3)


def resolve_mixing_dtype(name: str):
    """Shared "f32" | "bf16" vocabulary of the reference tier's
    `DAGMConfig.mixing_dtype` and the sharded tier's
    `ShardedDAGMConfig.comm_dtype`: returns the jnp storage/wire dtype,
    or None for full precision (no quantization)."""
    if name == "f32":
        return None
    if name == "bf16":
        return jnp.bfloat16
    raise ValueError(f"unknown mixing dtype {name!r}; "
                     f"expected one of {MIXING_DTYPES}")


class MixingOp:
    """Topology-aware executor for W·Y, (I−W)·Y and the fused DIHGP
    Neumann step on stacked per-agent states (see module docstring).

    Backend resolution happens once, at construction (Python level), so
    inside jitted hot loops the dispatch is free.  The operator is
    linear; the Pallas tiers do not register a VJP (the algorithm stack
    uses explicit gradients, never autodiff through the mixing), while
    the dense, circulant and sparse_gather XLA tiers remain fully
    differentiable.  Because of that, an *explicitly requested*
    "circulant" / "sparse_gather" backend never silently upgrades to
    Pallas — only "auto" does, when `repro.kernels.ops.use_pallas(True)`
    is set.
    """

    def __init__(self, W, *, backend: str = "auto",
                 interpret: bool | None = None,
                 name: str = "network",
                 dtype: str = "f32", comm: str = "identity"):
        from repro.comm import CommLedger, parse_comm_spec
        if backend not in BACKENDS:
            raise ValueError(f"unknown mixing backend {backend!r}; "
                             f"expected one of {BACKENDS}")
        self.W = jnp.asarray(W, jnp.float32)
        self.name = name
        self.interpret = interpret
        self.requested = backend
        self.dtype = dtype
        self.storage_dtype = resolve_mixing_dtype(dtype)
        self.comm = parse_comm_spec(comm)
        self.ledger = CommLedger(name)
        self._diag = jnp.diag(self.W)
        self.structure = circulant_structure(W)
        self.sparse = sparse_structure(W)
        self._masked_cache = None
        if backend == "auto":
            s, sp = self.structure, self.sparse
            if s is not None and 2 * (len(s.offsets) + 1) <= s.n:
                self.backend = "circulant"
            elif sp is not None and sp.nnz + sp.n < sp.n * sp.n:
                self.backend = "sparse_gather"
            else:
                self.backend = "dense"
        elif backend in ("circulant", "circulant_pallas") \
                and self.structure is None:
            raise ValueError(
                f"backend {backend!r} requires a circulant W "
                f"(ring/circulant topology); got a non-shift-invariant "
                f"matrix — use 'sparse_gather', 'dense' or 'auto'")
        elif backend in ("sparse_gather", "sparse_gather_pallas") \
                and self.sparse is None:
            raise ValueError(
                f"backend {backend!r} requires a square mixing matrix "
                f"with n >= 2")
        else:
            self.backend = backend
        if self.backend in ("sparse_gather", "sparse_gather_pallas"):
            sp = self.sparse
            self._sp_wself = jnp.asarray(sp.w_self)
            self._sp_row = jnp.asarray(sp.row)
            self._sp_col = jnp.asarray(sp.col)
            self._sp_val = jnp.asarray(sp.val)
            self._sp_idx = jnp.asarray(sp.neighbors)
            self._sp_wts = jnp.asarray(sp.weights)
            # XLA formulation: padded row-gather loop when the degree
            # distribution is near-regular (its n·k_max work is within
            # 2× of the CSR nnz — ER graphs), CSR segment-sum when
            # skewed (star: k_max = n−1 but nnz = 2(n−1))
            self._sp_use_padded = sp.n * sp.k <= 2 * sp.nnz

    @property
    def n(self) -> int:
        return self.W.shape[0]

    def __repr__(self) -> str:
        if self.structure is not None:
            k = len(self.structure.offsets)
        elif self.sparse is not None:
            k = self.sparse.k
        else:
            k = None
        return (f"MixingOp({self.name}, n={self.n}, "
                f"backend={self.backend}, neighbors={k}, "
                f"dtype={self.dtype})")

    # -- dispatch ----------------------------------------------------------

    def _resolve(self, backend: str, flat: jnp.ndarray) -> str:
        """Concrete path for this call: honours the per-shape Pallas
        tiling constraints ("auto" upgrades when kernels.ops enables
        Pallas — with ops' interpret flag, since that switch owns the
        tier; an *explicitly requested* XLA backend never upgrades,
        staying differentiable.  Non-tile-multiple shapes fall back to
        dense for "circulant_pallas" and to the CSR XLA path for
        "sparse_gather_pallas")."""
        if backend in ("circulant", "sparse_gather") \
                and self.requested == "auto":
            # the sparse Pallas kernel walks the padded (n, k_max)
            # table, so on skewed-degree graphs (star) where the XLA
            # dispatch already rejected that formulation the upgrade
            # would regress O((nnz+n)·d) to O(n·k_max·d) — stay on CSR
            if backend == "sparse_gather" and not self._sp_use_padded:
                return backend
            from repro.kernels import ops as _ops
            enabled, interp = _ops.pallas_enabled()
            if enabled and self._pallas_ok(flat):
                self._interp_now = interp
                return backend + "_pallas"
            return backend
        if backend == "circulant_pallas":
            if self._pallas_ok(flat):
                self._interp_now = self.interpret
                return "circulant_pallas"
            self._warn_tiles(backend, flat)
            return "dense"
        if backend == "sparse_gather_pallas":
            if self._pallas_ok(flat):
                self._interp_now = self.interpret
                return "sparse_gather_pallas"
            self._warn_tiles(backend, flat)
            return "sparse_gather"
        return backend

    def _warn_tiles(self, backend: str, flat: jnp.ndarray) -> None:
        n, d = flat.shape
        _warn_pallas_fallback(
            self.name, backend,
            f"shape ({n}, {d}) dtype {flat.dtype} misses the tile "
            f"constraints (n % sublane == 0, d % 128 == 0)")

    def _pallas_ok(self, flat: jnp.ndarray) -> bool:
        n, d = flat.shape
        if flat.dtype == jnp.float32:
            sublane = 8
        elif flat.dtype == jnp.bfloat16:
            sublane = 16
        else:
            return False
        return n % sublane == 0 and d % 128 == 0

    def _stripe_plan(self, flat: jnp.ndarray, *, blocks: int,
                     circulant: bool):
        """("full", None) when the full-stripe kernel's resident
        (n, bd) blocks fit the VMEM budget, ("halo", bn) to run the
        row-tiled halo kernel, ("xla", None) when no tile qualifies
        (caller falls back + warns).  `blocks` is the number of live
        stripe-sized buffers of the chosen kernel variant (3 plain,
        4 fused, 6 fused+EF)."""
        from repro.kernels.mixing_matvec import (VMEM_BUDGET_BYTES,
                                                 halo_extents,
                                                 pick_halo_bn,
                                                 stripe_vmem_bytes)
        n = flat.shape[0]
        item = flat.dtype.itemsize
        if stripe_vmem_bytes(n, itemsize=item, blocks=blocks) \
                <= VMEM_BUDGET_BYTES:
            return "full", None
        sublane = 8 if flat.dtype == jnp.float32 else 16
        if circulant:
            h_lo, h_hi = halo_extents(self.structure.offsets, n)
        else:
            h_lo = h_hi = 0
        bn = pick_halo_bn(n, sublane=sublane, h_lo=h_lo, h_hi=h_hi,
                          itemsize=item, blocks=blocks)
        if bn is None:
            return "xla", None
        return "halo", bn

    # -- primitives --------------------------------------------------------

    def mix(self, y: jnp.ndarray) -> jnp.ndarray:
        """(W ⊗ I) y on stacked y of shape (n, ...)."""
        return self._apply(y, laplacian=False)

    def laplacian(self, y: jnp.ndarray) -> jnp.ndarray:
        """((I − W) ⊗ I) y."""
        return self._apply(y, laplacian=True)

    def _apply(self, y: jnp.ndarray, laplacian: bool) -> jnp.ndarray:
        flat = y.reshape(y.shape[0], -1)
        out_dtype = flat.dtype
        if self.storage_dtype is not None \
                and flat.dtype != self.storage_dtype:
            # bf16 storage: round the operand once; backends then
            # accumulate the rounded values in f32 (Pallas kernels do so
            # natively; the XLA paths get an explicit f32 upcast below).
            flat = flat.astype(self.storage_dtype)
        path = self._resolve(self.backend, flat)
        bn = None
        if path in ("circulant_pallas", "sparse_gather_pallas"):
            tier, bn = self._stripe_plan(
                flat, blocks=3, circulant=path == "circulant_pallas")
            if tier == "xla":
                _warn_pallas_fallback(
                    self.name, path,
                    f"n={flat.shape[0]} full stripe exceeds the VMEM "
                    f"budget and no halo row tile divides it")
                path = "circulant" if path == "circulant_pallas" \
                    else "sparse_gather"
        if path == "circulant_pallas":
            from repro.kernels.mixing_matvec import (
                circulant_mix_matvec, circulant_mix_matvec_halo)
            s = self.structure
            if bn is None:
                out = circulant_mix_matvec(flat, w_self=s.w_self,
                                           offsets=s.offsets,
                                           weights=s.weights,
                                           laplacian=laplacian,
                                           interpret=self._interp_now)
            else:
                out = circulant_mix_matvec_halo(flat, w_self=s.w_self,
                                                offsets=s.offsets,
                                                weights=s.weights,
                                                laplacian=laplacian,
                                                bn=bn,
                                                interpret=self._interp_now)
        elif path == "sparse_gather_pallas":
            from repro.kernels.mixing_matvec import (
                sparse_mix_matvec, sparse_mix_matvec_halo)
            if bn is None:
                out = sparse_mix_matvec(flat, self._sp_wself,
                                        self._sp_idx, self._sp_wts,
                                        laplacian=laplacian,
                                        interpret=self._interp_now)
            else:
                out = sparse_mix_matvec_halo(flat, self._sp_wself,
                                             self._sp_idx, self._sp_wts,
                                             laplacian=laplacian, bn=bn,
                                             interpret=self._interp_now)
        else:
            acc = flat if self.storage_dtype is None \
                else flat.astype(jnp.float32)
            if path == "dense":
                out = self.W.astype(acc.dtype) @ acc
                if laplacian:
                    out = acc - out
            elif path == "sparse_gather":
                from repro.kernels.ref import (sparse_mix_padded_ref,
                                               sparse_mix_ref)
                if self._sp_use_padded:
                    out = sparse_mix_padded_ref(acc, self._sp_wself,
                                                self._sp_idx,
                                                self._sp_wts,
                                                laplacian=laplacian)
                else:
                    out = sparse_mix_ref(acc, self._sp_wself,
                                         self._sp_row, self._sp_col,
                                         self._sp_val,
                                         laplacian=laplacian)
            else:
                from repro.kernels.ref import circulant_mix_ref
                s = self.structure
                out = circulant_mix_ref(acc, s.w_self, s.offsets,
                                        s.weights, laplacian=laplacian)
        if self.storage_dtype is not None:
            # round the result back through storage precision so every
            # backend returns identically-quantized values
            out = out.astype(self.storage_dtype)
        return out.astype(out_dtype).reshape(y.shape)

    def neumann_step(self, h: jnp.ndarray, hvp_h: jnp.ndarray,
                     p: jnp.ndarray, d_scalar: jnp.ndarray,
                     beta: float) -> jnp.ndarray:
        """Fused DIHGP iteration h⁺ = (D̃h − (I−W)h − β·hvp_h − p)/D̃.

        d_scalar: per-agent D̃ diagonal, broadcastable against h as
        (n,) + (1,)*… (see dihgp.dihgp_matrix_free)."""
        if not isinstance(beta, (int, float, np.floating)):
            # traced β (repro.solve runtime schedules): the Pallas
            # kernel bakes beta as a compile-time constant, so fold the
            # traced scalar into its operand instead — β·hvp_h with
            # β=1.0 in-kernel multiplies by exactly 1.0, value-exact
            hvp_h = beta * hvp_h
            beta = 1.0
        flat = h.reshape(h.shape[0], -1)
        path = self._resolve(self.backend, flat)
        if path == "circulant_pallas" and self.storage_dtype is None:
            from repro.kernels.mixing_matvec import circulant_neumann_step
            s = self.structure
            out = circulant_neumann_step(
                flat, hvp_h.reshape(flat.shape), p.reshape(flat.shape),
                d_scalar.reshape(h.shape[0], 1).astype(jnp.float32),
                w_self=s.w_self, offsets=s.offsets, weights=s.weights,
                beta=beta, interpret=self._interp_now)
            return out.reshape(h.shape)
        # sparse / bf16-storage tiers compose the same algebra from the
        # backend mix (only the W·h term is storage-quantized — the
        # local D̃/HVP/p terms never cross the wire)
        return _neumann_update(self._apply(h, laplacian=False), h, hvp_h,
                               p, d_scalar, beta)

    # -- compressed gossip (repro.comm) ------------------------------------

    def comm_channel(self, name: str, x, key):
        """Open a gossip channel for stacked variable template `x`:
        registers the payload shape in the ledger (eager, pre-trace)
        and returns the ChannelState to thread through the hot loop."""
        from repro.comm import channel_init
        self.ledger.register(name, x.shape[1:], self.comm)
        return channel_init(self.comm, name, x, key)

    # a MaskedMixingOp view must never take the fused kernels (the mask
    # breaks shift invariance and stays a traced operand)
    _fusable_view = True

    def _fused_plan(self, flat: jnp.ndarray):
        """(path, bn) when this gossip can run the comm-fused Pallas
        kernels (one VMEM traversal for compress→mix→decompress), None
        to keep the XLA compose path: non-fusable policy (identity /
        bf16 / top-k / rand-k), bf16 storage, non-f32 operand, masked
        view, shapes the kernels can't tile, or sparse halo + EF (no
        payload write-back in that variant).  bn=None → full stripe."""
        if not self._fusable_view or not self.comm.fusable \
                or self.storage_dtype is not None \
                or flat.dtype != jnp.float32:
            return None
        path = self._resolve(self.backend, flat)
        if path not in ("circulant_pallas", "sparse_gather_pallas"):
            return None
        ef = self.comm.ef
        tier, bn = self._stripe_plan(flat, blocks=6 if ef else 4,
                                     circulant=path == "circulant_pallas")
        if tier == "full":
            return path, None
        if tier == "halo":
            if path == "sparse_gather_pallas" and ef:
                _warn_pallas_fallback(
                    self.name, "fused sparse halo",
                    "'+ef' needs the full-stripe payload write-back; "
                    "running the XLA compose path")
                return None
            return path, bn
        _warn_pallas_fallback(
            self.name, "fused " + path,
            f"n={flat.shape[0]} full stripe exceeds the VMEM budget "
            f"and no halo row tile divides it")
        return None

    def _next_seed(self, st):
        """Advance the channel key exactly as `compressed_payload`
        does (split; first half becomes the new state key) and derive
        the traced int32 seed the kernels' counter PRNG consumes from
        the second half."""
        key, sub = jax.random.split(st.key)
        seed = jax.random.randint(sub, (1,), 0,
                                  jnp.iinfo(jnp.int32).max, jnp.int32)
        return key, seed

    def _apply_fused(self, y: jnp.ndarray, flat: jnp.ndarray, st,
                     laplacian: bool, plan):
        """One fused compress→mix→decompress gossip (see `_fused_plan`).

        Semantics mirror `compressed_payload` + `_apply` exactly: same
        `row_quant_params` wire metadata, same state advance (key split,
        sends + 1, hat ← payload under EF) — only the source of the
        stochastic-rounding uniforms differs (in-kernel counter PRNG
        instead of `jax.random.uniform`), which the quantizer's
        unbiasedness contract makes statistically equivalent."""
        from repro.comm import row_quant_params
        from repro.kernels.mixing_matvec import (
            circulant_mix_matvec, circulant_mix_matvec_halo,
            sparse_mix_matvec, sparse_mix_matvec_halo)
        path, bn = plan
        bits = self.comm.compressor.bits
        ef = self.comm.ef
        comm = f"int{bits}" + ("+ef" if ef else "")
        key, seed = self._next_seed(st)
        hat = st.hat.reshape(flat.shape) if ef else None
        src = flat - hat if ef else flat
        zp, scale = row_quant_params(src, bits)
        if path == "circulant_pallas":
            s = self.structure
            kw = dict(w_self=s.w_self, offsets=s.offsets,
                      weights=s.weights, laplacian=laplacian, comm=comm,
                      interpret=self._interp_now)
            if bn is None:
                res = circulant_mix_matvec(flat, zp, scale, seed, hat,
                                           **kw)
            else:
                res = circulant_mix_matvec_halo(flat, zp, scale, seed,
                                                hat, bn=bn, **kw)
        elif bn is None:
            res = sparse_mix_matvec(flat, self._sp_wself, self._sp_idx,
                                    self._sp_wts, zp, scale, seed, hat,
                                    laplacian=laplacian, comm=comm,
                                    interpret=self._interp_now)
        else:
            res = sparse_mix_matvec_halo(flat, self._sp_wself,
                                         self._sp_idx, self._sp_wts,
                                         zp, scale, seed,
                                         laplacian=laplacian, bn=bn,
                                         comm=comm,
                                         interpret=self._interp_now)
        if ef:
            out, pay = res
            st = dataclasses.replace(st, hat=pay.reshape(y.shape),
                                     key=key, sends=st.sends + 1)
        else:
            out = res
            st = dataclasses.replace(st, key=key, sends=st.sends + 1)
        return out.astype(y.dtype).reshape(y.shape), st

    def _apply_c(self, y: jnp.ndarray, st, laplacian: bool):
        """compress→mix→decompress around one gossip of y (n, ...).

        The neighbors mix the decoded payload ŷ; the self-weight term
        w_ii·y_i never crosses the wire, so the backend result W·ŷ is
        corrected by diag(W)·(y − ŷ) before the (I−W) algebra.  When
        the policy is a fusable quantizer and the Pallas tier is active
        the whole sequence runs inside the mixing kernel instead
        (`_fused_plan` / `_apply_fused`)."""
        from repro.comm import compressed_payload
        if self.comm.is_identity:
            return self._apply(y, laplacian), st.bump()
        flat = y.reshape(y.shape[0], -1)
        plan = self._fused_plan(flat)
        if plan is not None:
            return self._apply_fused(y, flat, st, laplacian, plan)
        y_hat, st = compressed_payload(self.comm, y, st)
        mixed = self._apply(y_hat, laplacian=False)
        expand = (slice(None),) + (None,) * (y.ndim - 1)
        mixed = mixed + self._diag[expand].astype(y.dtype) * (y - y_hat)
        return (y - mixed) if laplacian else mixed, st

    def mix_c(self, y: jnp.ndarray, st):
        """(W ⊗ I) y through the compressed channel -> (out, state)."""
        return self._apply_c(y, st, laplacian=False)

    def laplacian_c(self, y: jnp.ndarray, st):
        """((I − W) ⊗ I) y through the compressed channel."""
        return self._apply_c(y, st, laplacian=True)

    def neumann_step_c(self, h, hvp_h, p, d_scalar, beta: float, st):
        """Fused DIHGP step with the W·h gossip compressed; identity
        policy keeps today's fused path (Pallas tier included).  A
        fusable non-EF quantizer on the full-stripe circulant tier runs
        the comm-fused Neumann kernel — quantize + mix + the whole
        Eq. 14 update in one traversal; EF and the other tiers compose
        `mix_c` (itself fused when possible) with the XLA update."""
        if self.comm.is_identity:
            return self.neumann_step(h, hvp_h, p, d_scalar, beta), \
                st.bump()
        if not self.comm.ef and self.storage_dtype is None:
            flat = h.reshape(h.shape[0], -1)
            plan = self._fused_plan(flat)
            if plan is not None and plan[0] == "circulant_pallas" \
                    and plan[1] is None:
                from repro.comm import row_quant_params
                from repro.kernels.mixing_matvec import \
                    circulant_neumann_step
                if not isinstance(beta, (int, float, np.floating)):
                    hvp_h = beta * hvp_h
                    beta = 1.0
                key, seed = self._next_seed(st)
                bits = self.comm.compressor.bits
                zp, scale = row_quant_params(flat, bits)
                s = self.structure
                out = circulant_neumann_step(
                    flat, hvp_h.reshape(flat.shape),
                    p.reshape(flat.shape),
                    d_scalar.reshape(h.shape[0], 1).astype(jnp.float32),
                    zp, scale, seed, w_self=s.w_self, offsets=s.offsets,
                    weights=s.weights, beta=beta, comm=f"int{bits}",
                    interpret=self._interp_now)
                st = dataclasses.replace(st, key=key,
                                         sends=st.sends + 1)
                return out.reshape(h.shape), st
        mix, st = self.mix_c(h, st)
        return _neumann_update(mix, h, hvp_h, p, d_scalar, beta), st

    # -- fault-masked mixing (repro.faults) --------------------------------

    def _masked_tables(self):
        """Padded-table jnp constants (w_self, neighbors, weights) — the
        operand space per-round fault masks degrade (lazily cached; the
        tables exist even when the resolved backend is dense/circulant,
        since `sparse_structure` covers any square W with n >= 2)."""
        if self._masked_cache is None:
            sp = self.sparse
            if sp is None:
                raise ValueError(
                    f"fault masks need the padded sparse tables, which "
                    f"require a square mixing matrix with n >= 2 (got "
                    f"n={self.n})")
            self._masked_cache = (jnp.asarray(sp.w_self),
                                  jnp.asarray(sp.neighbors),
                                  jnp.asarray(sp.weights))
        return self._masked_cache

    def masked(self, mask) -> "MaskedMixingOp":
        """This round's degraded view of the op: mask is (n, k_max) in
        the padded `sparse_structure` table layout (1 = link alive, 0 =
        dropped; symmetric in edge space — see repro.faults).  Cheap at
        trace time; build one per scanned round."""
        return MaskedMixingOp(self, mask)

    def mix_masked(self, y: jnp.ndarray, mask) -> jnp.ndarray:
        """(W_k ⊗ I) y under a per-round fault mask (see `masked`)."""
        return self.masked(mask).mix(y)

    def laplacian_masked(self, y: jnp.ndarray, mask) -> jnp.ndarray:
        """((I − W_k) ⊗ I) y under a per-round fault mask."""
        return self.masked(mask).laplacian(y)


class MaskedMixingOp(MixingOp):
    """A per-round degraded view of a base MixingOp (see `MixingOp
    .masked`): applies W_k = W ⊙ M with dropped weight folded into the
    self-weight, in the padded neighbor-table space.

    Shares the base op's comm policy / ledger / channel bookkeeping by
    reference and overrides only the gossip algebra; every apply runs
    the padded row-gather formulation regardless of the base backend
    (masks break shift invariance, and the Pallas kernels bake their
    weight tables as compile-time constants — the mask must stay a
    traced operand for the zero-retrace contract)."""

    _fusable_view = False     # comm-fused kernels never see a mask

    def __init__(self, base: MixingOp, mask):
        self.__dict__.update(base.__dict__)  # view: share, don't rebuild
        w_self, idx, wts = base._masked_tables()
        mask = jnp.asarray(mask, wts.dtype)
        if mask.shape != idx.shape:
            raise ValueError(
                f"fault mask shape {mask.shape} does not match the "
                f"padded neighbor table {tuple(idx.shape)} of "
                f"{base.name}; lower it with FaultTrace.table_masks")
        self._m_idx = idx
        # all-ones mask ⇒ wts·1.0 and w_self+0.0 are bitwise no-ops, so
        # the unfaulted view reproduces the padded path bit-exactly
        self._m_wts = wts * mask
        self._m_wself = w_self + jnp.sum(wts * (1.0 - mask), axis=1)

    def __repr__(self) -> str:
        return (f"MaskedMixingOp({self.name}, n={self.n}, "
                f"backend=sparse_gather[masked], dtype={self.dtype})")

    def _apply(self, y: jnp.ndarray, laplacian: bool) -> jnp.ndarray:
        from repro.kernels.ref import sparse_mix_padded_ref
        flat = y.reshape(y.shape[0], -1)
        out_dtype = flat.dtype
        if self.storage_dtype is not None \
                and flat.dtype != self.storage_dtype:
            flat = flat.astype(self.storage_dtype)
        acc = flat if self.storage_dtype is None \
            else flat.astype(jnp.float32)
        out = sparse_mix_padded_ref(acc, self._m_wself, self._m_idx,
                                    self._m_wts, laplacian=laplacian)
        if self.storage_dtype is not None:
            out = out.astype(self.storage_dtype)
        return out.astype(out_dtype).reshape(y.shape)

    def _apply_c(self, y: jnp.ndarray, st, laplacian: bool):
        # same compress→mix→decompress contract as the base, but the
        # never-on-the-wire self term uses the *effective* self-weight
        # (nominal w_ii plus this round's folded-back dropped weight)
        from repro.comm import compressed_payload
        if self.comm.is_identity:
            return self._apply(y, laplacian), st.bump()
        y_hat, st = compressed_payload(self.comm, y, st)
        mixed = self._apply(y_hat, laplacian=False)
        expand = (slice(None),) + (None,) * (y.ndim - 1)
        mixed = mixed + self._m_wself[expand].astype(y.dtype) \
            * (y - y_hat)
        return (y - mixed) if laplacian else mixed, st

    def neumann_step(self, h, hvp_h, p, d_scalar, beta):
        if not isinstance(beta, (int, float, np.floating)):
            hvp_h = beta * hvp_h
            beta = 1.0
        return _neumann_update(self._apply(h, laplacian=False), h,
                               hvp_h, p, d_scalar, beta)


def make_mixing_op(net: "Network", backend: str = "auto",
                   interpret: bool | None = None,
                   dtype: str = "f32",
                   comm: str = "identity") -> MixingOp:
    """Build the execution backend for a validated Network."""
    return MixingOp(net.W, backend=backend, interpret=interpret,
                    name=net.name, dtype=dtype, comm=comm)


def as_matrix(W) -> jnp.ndarray:
    """Raw (n, n) mixing matrix from either a MixingOp or an array —
    for reference-tier code that needs W entries (diag, kron, eig)."""
    return W.W if isinstance(W, MixingOp) else W


# ---------------------------------------------------------------------------
# Applying W to stacked per-agent states (free-function façade)
# ---------------------------------------------------------------------------

def mix_apply(W, y: jnp.ndarray) -> jnp.ndarray:
    """(W ⊗ I_d) y for stacked y of shape (n, d) [or (n, ...)].

    W may be a raw (n, n) array (dense matmul) or a MixingOp (backend
    dispatch) — every hot-loop caller routes through here, so each
    gossip carries the `gossip` named scope in the compiled program."""
    with jax.named_scope("gossip"):
        if isinstance(W, MixingOp):
            return W.mix(y)
        flat = y.reshape(y.shape[0], -1)
        out = W.astype(flat.dtype) @ flat
        return out.reshape(y.shape)


def laplacian_apply(W, y: jnp.ndarray) -> jnp.ndarray:
    """((I - W) ⊗ I_d) y — the penalty-gradient mixing term."""
    with jax.named_scope("gossip"):
        if isinstance(W, MixingOp):
            return W.laplacian(y)
        return y - mix_apply(W, y)


def _neumann_update(mix, h, hvp_h, p, d_scalar, beta: float):
    """Shared fused-step algebra, given the mixed state mix = W·h:

        h⁺ = (D̃h − (h − W h) − β·hvp_h − p) / D̃

    Single source of truth for every non-Pallas tier (the Pallas kernel
    computes the identical expression in `_neumann_body`)."""
    return (d_scalar * h - (h - mix) - beta * hvp_h - p) / d_scalar


def fused_neumann_step(W, h, hvp_h, p, d_scalar, beta: float):
    """One DIHGP Neumann iteration (Eq. 14) in a single traversal:

        h⁺ = (D̃h − (I−W)h − β·hvp_h − p) / D̃

    MixingOp dispatches to the fused Pallas kernel on the circulant
    tier; the array/dense path composes the same algebra in XLA."""
    with jax.named_scope("gossip"):
        if isinstance(W, MixingOp):
            return W.neumann_step(h, hvp_h, p, d_scalar, beta)
        return _neumann_update(mix_apply(W, h), h, hvp_h, p, d_scalar,
                               beta)


# ---------------------------------------------------------------------------
# Compressed-channel façade (repro.comm): every caller threads a
# ChannelState and gets (result, state) back.  Raw W arrays carry no
# comm policy, so they gossip uncompressed (the dense reference path);
# a MixingOp applies whatever its `comm=` spec says — call sites stay
# branch-free either way.
# ---------------------------------------------------------------------------

def mix_apply_c(W, y: jnp.ndarray, st):
    """(W ⊗ I) y through the gossip channel -> (mixed, state)."""
    with jax.named_scope("gossip"):
        if isinstance(W, MixingOp):
            return W.mix_c(y, st)
        return mix_apply(W, y), st.bump()


def laplacian_apply_c(W, y: jnp.ndarray, st):
    """((I − W) ⊗ I) y through the gossip channel -> (out, state)."""
    with jax.named_scope("gossip"):
        if isinstance(W, MixingOp):
            return W.laplacian_c(y, st)
        return laplacian_apply(W, y), st.bump()


def fused_neumann_step_c(W, h, hvp_h, p, d_scalar, beta: float, st):
    """Compressed-channel twin of `fused_neumann_step`."""
    with jax.named_scope("gossip"):
        if isinstance(W, MixingOp):
            return W.neumann_step_c(h, hvp_h, p, d_scalar, beta, st)
        return _neumann_update(mix_apply(W, h), h, hvp_h, p, d_scalar,
                               beta), st.bump()
