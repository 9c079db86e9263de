"""Pallas TPU kernels for the paper's hot communication/compute
primitive: banded-circulant mixing mat-vecs on stacked per-agent state
Y ∈ R^{n×d} — W·Y, (I−W)·Y (DAGM inner step Eq. 16, penalty gradients)
and the fused DIHGP Neumann step h ← (D̃h − Hh − p)/D̃ (Eq. 14).

For the shift-invariant graphs the paper benchmarks (ring, 2k-regular
circulant), row i of W is a cyclic shift of row 0: w_self on the
diagonal and weight c_o at offset o, so

    (W·Y)_i = w_self·Y_i + Σ_o c_o · Y_{(i+o) mod n}

is O(n·k·d) neighbor-only work — the same data movement the algorithm
performs across chips, here expressed inside a chip.

Layout choice: the agent axis n is small (8–4096) next to the feature
axis d (10³–10⁸ once model parameters are raveled), so the kernels tile
the *feature* axis — grid (d/bd,) — and keep the full agent axis of one
column stripe resident in VMEM.  Each program reads its input stripe
exactly once and applies the offsets as in-register cyclic shifts (two
static sublane slices + a concatenate — no gather, no MXU).
Accumulation is f32 regardless of input dtype (f32/bf16 supported).

Row-tiled halo variants (`*_halo`)
----------------------------------
The full-stripe layout caps n near 10⁴ ((n, bd)·4B·#blocks against the
~4 MB `VMEM_BUDGET_BYTES`).  The `*_halo` kernels tile the agent axis
too — grid (n/bn, d/bd) — holding only a (bn, bd) row tile plus its
neighbor halo: the operand stays in HBM (`pl.ANY`) and each program
DMAs three contiguous row ranges (low halo, main rows, high halo) into
a VMEM scratch of (h_lo + bn + h_hi, bd) rows, after which every cyclic
offset is a *static* sublane slice of the extended block.  Because
bn | n and the halo extents never exceed bn, none of the three copies
wraps.  Accumulation order per element is identical to the full-stripe
kernel, so the two variants agree bitwise for any bn.  The sparse halo
variant instead DMAs each neighbor row (1, bd) on demand from the
scalar-prefetched index table — same bitwise-agreement property.

Fused compressed gossip (`comm=`)
---------------------------------
`circulant_mix_matvec` / `sparse_mix_matvec` (and their halo twins, and
`circulant_neumann_step`) accept ``comm="int8" | "int4" | "int8+ef" |
"int4+ef"``: the `repro.comm.StochasticQuantCompressor` roundtrip is
applied to the *neighbor* rows inside the kernel — per-row zero-point /
scale (precomputed by `repro.comm.row_quant_params`, the bitwise-shared
wire-metadata helper, and passed as (n, 1) operands) plus in-kernel
stochastic rounding — while the self-weight term w_self·Y_i, which
never crosses the wire, stays exact.  One VMEM traversal then performs
compress→mix→decompress instead of the three HBM round-trips of the
XLA compose path (see `benchmarks/roofline.py:mixing_traffic_model`).
With ``+ef`` the kernel also takes the CHOCO replica `hat` and returns
``(out, payload)`` with payload = hat + C(y − hat), so the caller can
advance `ChannelState.hat` exactly as `repro.comm.compressed_payload`
would.

Stochastic rounding uniforms come from a counter PRNG keyed on (seed,
global row, global column) — a murmur3 finalizer over the element
position (`prng="hash"`, the default): every tiling (full-stripe or
halo, any bn/bd) draws the *same* uniform for the same element, so the
quantized payload is bitwise-reproducible across grid layouts (the
mixed output agrees up to compiler FMA re-association, ≤ 1 ulp) and
the whole path is testable in interpret mode.  ``prng="pltpu"`` switches to
the TPU hardware PRNG (`pltpu.prng_seed` / `prng_random_bits`, seeded
from the traced key operand + program ids) for real-hardware runs; it
is statistically equivalent but per-program-seeded, and does not lower
in interpret mode.  Either way the draws satisfy the quantizer's
unbiasedness contract E⌊z + u⌋ = z.

Entry points
------------
* `circulant_mix_matvec[_halo]` — W·Y or (I−W)·Y for offset sets,
                                  optionally comm-fused.
* `sparse_mix_matvec[_halo]`    — the same for arbitrary sparse W via
                                  per-row neighbor gather (padded CSR).
* `circulant_neumann_step`      — one fused DIHGP iteration
                                  h⁺ = (D̃h − (I−W)h − β·Hvp − p)/D̃,
                                  optionally with the W·h gossip
                                  quantized in-kernel (non-EF comm).
* `ring_laplacian_matvec`       — backward-compatible ring wrapper.

Dispatch policy (which variant runs when — including the VMEM-budget
full-stripe→halo switch via `pick_halo_bn`) lives in
`repro.topology.ops.MixingOp`; these functions assume tile-friendly
shapes and raise on anything else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import pallas_interpret

# Conservative per-program VMEM working-set budget (real cores have
# ~16 MB, shared with pipelining double-buffers): the dispatch switches
# from full-stripe to halo tiling when the resident blocks exceed this.
VMEM_BUDGET_BYTES = 4 * 1024 * 1024

KERNEL_COMMS = ("int8", "int4", "int8+ef", "int4+ef")


def _parse_kernel_comm(comm: str | None) -> tuple[int, bool] | None:
    """(bits, ef) for a fusable comm spec; None for the unfused path."""
    if comm in (None, "identity"):
        return None
    base, _, opt = str(comm).partition("+")
    bits = {"int8": 8, "int4": 4}.get(base)
    if bits is None or opt not in ("", "ef"):
        raise ValueError(
            f"comm={comm!r} is not kernel-fusable; expected one of "
            f"{KERNEL_COMMS} (identity/top-k/rand-k/bf16 gossip stays "
            f"on the XLA compose path — see MixingOp)")
    return bits, opt == "ef"


# ---------------------------------------------------------------------------
# In-kernel stochastic-rounding uniforms
# ---------------------------------------------------------------------------

def _fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer — full avalanche on the VPU."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _top24_uniform(bits: jnp.ndarray) -> jnp.ndarray:
    """U[0,1) f32 from the top 24 bits of uint32 draws.  The shifted
    value fits in 24 bits, so the detour through int32 is exact; Mosaic
    has no direct uint32 -> f32 cast."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _hash_uniform(seed, rows, cols) -> jnp.ndarray:
    """U[0,1) f32 draws keyed on (seed, global row, global column).

    Position-keyed counter PRNG: the same element gets the same draw in
    every grid layout, which is what makes full-stripe and halo fused
    kernels agree bitwise.  24 mantissa-exact bits per draw.
    """
    base = rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) \
        + cols.astype(jnp.uint32)
    h = _fmix32(base ^ (seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)))
    h = _fmix32(h)
    return _top24_uniform(h)


def _block_uniform(seed, rows, cols, shape, prng: str, pids=()):
    """Uniforms for one resident block: rows/cols are the *global*
    element coordinates (broadcastable to `shape`)."""
    if prng == "pltpu":
        pltpu.prng_seed(seed, *pids)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
        return _top24_uniform(bits)
    return jnp.broadcast_to(_hash_uniform(seed, rows, cols), shape)


def _quantize(x, zp, scale, u, levels: float):
    """Decoded stochastic-quantizer roundtrip of x given per-row wire
    metadata — the in-kernel twin of StochasticQuantCompressor
    .roundtrip (identical formula; u replaces jax.random.uniform)."""
    q = jnp.clip(jnp.floor((x - zp) / scale + u), 0.0, levels)
    return zp + scale * q


# ---------------------------------------------------------------------------
# Halo geometry + VMEM-budget planning (consumed by MixingOp dispatch)
# ---------------------------------------------------------------------------

def signed_offsets(offsets, n: int) -> tuple[int, ...]:
    """Cyclic offsets 0 < o < n remapped to the shorter direction
    (o ≤ n//2 stays +o, else o−n) — the halo extents follow."""
    return tuple(o if o <= n // 2 else o - n for o in offsets)


def halo_extents(offsets, n: int) -> tuple[int, int]:
    """(h_lo, h_hi): rows of low/high halo a row tile needs."""
    signed = signed_offsets(offsets, n)
    h_lo = max((-s for s in signed if s < 0), default=0)
    h_hi = max((s for s in signed if s > 0), default=0)
    return h_lo, h_hi


def stripe_vmem_bytes(n: int, bd: int = 128, itemsize: int = 4,
                      blocks: int = 3) -> int:
    """Resident VMEM estimate of a full-stripe program: `blocks` live
    (n, bd) buffers (input stripe, f32 accumulator, output, plus
    payload/replica blocks on the fused variants)."""
    return n * bd * itemsize * blocks


def pick_halo_bn(n: int, *, sublane: int = 8, h_lo: int = 0,
                 h_hi: int = 0, bd: int = 128, itemsize: int = 4,
                 blocks: int = 3,
                 budget: int = VMEM_BUDGET_BYTES) -> int | None:
    """Largest row-tile bn (descending powers of two ≥ sublane) with
    bn | n, halo extents ≤ bn (so no halo DMA wraps), and the extended
    block fitting the VMEM budget; None when no tile qualifies."""
    for bn in (2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if bn % sublane or n % bn or bn < max(h_lo, h_hi):
            continue
        if (h_lo + bn + h_hi) * bd * itemsize * blocks <= budget:
            return bn
    return None


# ---------------------------------------------------------------------------
# Full-stripe circulant kernel (plain + comm-fused)
# ---------------------------------------------------------------------------

def _shift(blk: jnp.ndarray, o: int) -> jnp.ndarray:
    """blk rows cyclically shifted so row i holds input row (i+o) mod n.

    Static slices + concatenate (≡ jnp.roll(blk, -o, axis=0)): lowers to
    sublane copies on TPU and plain lax.slice in interpret mode.
    """
    n = blk.shape[0]
    o = o % n
    if o == 0:
        return blk
    return jnp.concatenate([blk[o:], blk[:o]], axis=0)


def _mix_body(y_ref, out_ref, *, w_self, offsets, weights, laplacian):
    y = y_ref[...]
    acc = y.astype(jnp.float32) * w_self
    for o, c in zip(offsets, weights):
        acc = acc + c * _shift(y, o).astype(jnp.float32)
    if laplacian:
        acc = y.astype(jnp.float32) - acc
    out_ref[...] = acc.astype(out_ref.dtype)


def _mix_fused_body(seed_ref, zp_ref, scale_ref, *refs, w_self, offsets,
                    weights, laplacian, levels, ef, bd, prng):
    """compress→mix→decompress over one resident (n, bd) stripe.

    The stripe's payload is quantized ONCE per program — every consumer
    row sees the same decoded values, matching the one-broadcast-per-
    agent wire protocol — and the self term uses the exact y."""
    if ef:
        y_ref, hat_ref, out_ref, pay_ref, pay_scr = refs
    else:
        y_ref, out_ref, pay_scr = refs
    n = y_ref.shape[0]
    j = pl.program_id(0)
    y = y_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, bd), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, bd), 1) + j * bd
    u = _block_uniform(seed_ref[0], rows, cols, (n, bd), prng, pids=(j,))
    if ef:
        hat = hat_ref[...].astype(jnp.float32)
        pay_scr[...] = hat + _quantize(y - hat, zp_ref[...],
                                       scale_ref[...], u, levels)
    else:
        pay_scr[...] = _quantize(y, zp_ref[...], scale_ref[...], u,
                                 levels)
    # materialize the payload before mixing: compilers can't re-fuse
    # the quantize into the FMA chain, so full-stripe and halo tilings
    # contract the accumulation identically (bitwise agreement)
    pay = pay_scr[...]
    acc = y * w_self
    for o, c in zip(offsets, weights):
        acc = acc + c * _shift(pay, o)
    if laplacian:
        acc = y - acc
    out_ref[...] = acc.astype(out_ref.dtype)
    if ef:
        pay_ref[...] = pay.astype(pay_ref.dtype)


@functools.partial(jax.jit, static_argnames=("w_self", "offsets",
                                             "weights", "laplacian",
                                             "bd", "interpret", "comm",
                                             "prng"))
def circulant_mix_matvec(y: jnp.ndarray, zp=None, scale=None, seed=None,
                         hat=None, *, w_self: float,
                         offsets: tuple[int, ...],
                         weights: tuple[float, ...],
                         laplacian: bool = False, bd: int = 128,
                         interpret: bool | None = None,
                         comm: str | None = None, prng: str = "hash"):
    """W·Y (or (I−W)·Y) for circulant W; y: (n, d) with d % bd == 0.

    `offsets`/`weights`: W[i, (i+o) mod n] = c_o (offsets need not be
    symmetric; 0 < o < n).  w_self = W[i, i].

    `comm` lowering (see module docstring): zp/scale are the (n, 1)
    per-row wire metadata from `repro.comm.row_quant_params`, seed a
    traced (1,) int32 derived from the channel key.  With ``+ef`` pass
    the CHOCO replica `hat` (n, d); returns (out, payload) instead of
    out.  Neighbor rows are quantized in-kernel; the self term is exact.
    """
    interpret = pallas_interpret(interpret)
    n, d = y.shape
    if d % bd:
        raise ValueError(f"d={d} not a multiple of bd={bd}")
    fused = _parse_kernel_comm(comm)
    if fused is None:
        grid_spec = pl.GridSpec(
            grid=(d // bd,),
            in_specs=[pl.BlockSpec((n, bd), lambda j: (0, j))],
            out_specs=pl.BlockSpec((n, bd), lambda j: (0, j)),
        )
        body = functools.partial(_mix_body, w_self=float(w_self),
                                 offsets=tuple(offsets),
                                 weights=tuple(float(c) for c in weights),
                                 laplacian=laplacian)
        return pl.pallas_call(body, grid_spec=grid_spec,
                              out_shape=jax.ShapeDtypeStruct((n, d),
                                                             y.dtype),
                              interpret=interpret)(y)
    bits, ef = fused
    if prng == "pltpu" and interpret:
        raise ValueError("prng='pltpu' needs compiled TPU lowering; "
                         "interpret mode uses prng='hash'")
    stripe = pl.BlockSpec((n, bd), lambda j, *_: (0, j))
    vec = pl.BlockSpec((n, 1), lambda j, *_: (0, 0))
    in_specs = [vec, vec, stripe] + ([stripe] if ef else [])
    out_shape = jax.ShapeDtypeStruct((n, d), y.dtype)
    if ef:
        out_specs = (stripe, stripe)
        out_shape = (out_shape, jax.ShapeDtypeStruct((n, d), jnp.float32))
    else:
        out_specs = stripe
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(d // bd,),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)])
    body = functools.partial(_mix_fused_body, w_self=float(w_self),
                             offsets=tuple(offsets),
                             weights=tuple(float(c) for c in weights),
                             laplacian=laplacian,
                             levels=float(2 ** bits - 1), ef=ef, bd=bd,
                             prng=prng)
    operands = (seed.reshape(-1).astype(jnp.int32), zp, scale, y) \
        + ((hat,) if ef else ())
    return pl.pallas_call(body, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(*operands)


# ---------------------------------------------------------------------------
# Row-tiled halo circulant kernel (plain + comm-fused)
# ---------------------------------------------------------------------------

def _ext_copy(src, ext, sem, srow: int, *, row0, n, bn, h_lo, h_hi,
              col0, bd):
    """Start the (up to) three halo DMAs from an HBM-resident operand
    into the (h_lo + bn + h_hi, bd) VMEM scratch; returns the copy
    descriptors to wait on.  With bn | n and h_lo, h_hi ≤ bn none of
    the dynamic-start/static-size copies crosses the row boundary."""
    copies = []
    if h_lo:
        lo = jax.lax.rem(row0 - h_lo + n, n)
        copies.append(pltpu.make_async_copy(
            src.at[pl.ds(lo, h_lo), pl.ds(col0, bd)],
            ext.at[pl.ds(0, h_lo), :], sem.at[srow, 0]))
    copies.append(pltpu.make_async_copy(
        src.at[pl.ds(row0, bn), pl.ds(col0, bd)],
        ext.at[pl.ds(h_lo, bn), :], sem.at[srow, 1]))
    if h_hi:
        hi = jax.lax.rem(row0 + bn, n)
        copies.append(pltpu.make_async_copy(
            src.at[pl.ds(hi, h_hi), pl.ds(col0, bd)],
            ext.at[pl.ds(h_lo + bn, h_hi), :], sem.at[srow, 2]))
    for c in copies:
        c.start()
    return copies


def _ext_rows_vec(ref, row0, *, n, bn, h_lo, h_hi):
    """The (h_lo + bn + h_hi, 1) slice of a full (n, 1) VMEM vector
    matching the halo-extended rows (same three-range decomposition as
    the DMAs, as dynamic-start static-size reads)."""
    parts = []
    if h_lo:
        parts.append(ref[pl.ds(jax.lax.rem(row0 - h_lo + n, n), h_lo)])
    parts.append(ref[pl.ds(row0, bn)])
    if h_hi:
        parts.append(ref[pl.ds(jax.lax.rem(row0 + bn, n), h_hi)])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _circ_halo_body(*refs, n, bn, bd, h_lo, h_hi, w_self, signed,
                    weights, laplacian, levels, ef, fused, prng):
    if fused and ef:
        (seed_ref, zp_ref, scale_ref, y_hbm, hat_hbm, out_ref, pay_ref,
         ext, hext, pscr, sem) = refs
    elif fused:
        seed_ref, zp_ref, scale_ref, y_hbm, out_ref, ext, pscr, sem = refs
    else:
        y_hbm, out_ref, ext, sem = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    row0 = i * bn
    col0 = j * bd
    ex = h_lo + bn + h_hi
    copies = _ext_copy(y_hbm, ext, sem, 0, row0=row0, n=n, bn=bn,
                       h_lo=h_lo, h_hi=h_hi, col0=col0, bd=bd)
    if fused and ef:
        copies += _ext_copy(hat_hbm, hext, sem, 1, row0=row0, n=n, bn=bn,
                            h_lo=h_lo, h_hi=h_hi, col0=col0, bd=bd)
    for c in copies:
        c.wait()
    blk = ext[...].astype(jnp.float32)
    y = blk[h_lo:h_lo + bn]
    if fused:
        # global element coordinates of the extended block, so the
        # position-keyed uniforms match the full-stripe fused kernel
        t = jax.lax.broadcasted_iota(jnp.int32, (ex, bd), 0)
        rows = jax.lax.rem(row0 - h_lo + t + n, n)
        cols = jax.lax.broadcasted_iota(jnp.int32, (ex, bd), 1) + col0
        u = _block_uniform(seed_ref[0], rows, cols, (ex, bd), prng,
                           pids=(i, j))
        zp = _ext_rows_vec(zp_ref, row0, n=n, bn=bn, h_lo=h_lo, h_hi=h_hi)
        sc = _ext_rows_vec(scale_ref, row0, n=n, bn=bn, h_lo=h_lo,
                           h_hi=h_hi)
        if ef:
            hat = hext[...].astype(jnp.float32)
            pscr[...] = hat + _quantize(blk - hat, zp, sc, u, levels)
        else:
            pscr[...] = _quantize(blk, zp, sc, u, levels)
        # materialized payload — same FMA contraction as the
        # full-stripe fused body (see _mix_fused_body)
        pay = pscr[...]
    else:
        pay = blk
    acc = y * w_self
    for s, c in zip(signed, weights):
        acc = acc + c * pay[h_lo + s: h_lo + s + bn]
    if laplacian:
        acc = y - acc
    out_ref[...] = acc.astype(out_ref.dtype)
    if fused and ef:
        pay_ref[...] = pay[h_lo:h_lo + bn].astype(pay_ref.dtype)


@functools.partial(jax.jit, static_argnames=("w_self", "offsets",
                                             "weights", "laplacian",
                                             "bn", "bd", "interpret",
                                             "comm", "prng"))
def circulant_mix_matvec_halo(y: jnp.ndarray, zp=None, scale=None,
                              seed=None, hat=None, *, w_self: float,
                              offsets: tuple[int, ...],
                              weights: tuple[float, ...],
                              laplacian: bool = False, bn: int = 256,
                              bd: int = 128, interpret: bool | None = None,
                              comm: str | None = None,
                              prng: str = "hash"):
    """Row-tiled twin of `circulant_mix_matvec`: grid (n/bn, d/bd), the
    operand stays in HBM and each program holds only its (bn, bd) tile
    plus the neighbor halo — removing the full-stripe n ≈ 10⁴ VMEM
    ceiling.  Bitwise-identical to the full-stripe kernel for any valid
    bn on the plain path; the comm-fused path draws the same uniforms
    (position-keyed PRNG) so its payload is bitwise-identical too, and
    the mixed output agrees to ≤ 1 ulp (compiler FMA re-association).
    Requires bn | n and halo extents ≤ bn."""
    interpret = pallas_interpret(interpret)
    n, d = y.shape
    if d % bd:
        raise ValueError(f"d={d} not a multiple of bd={bd}")
    if n % bn:
        raise ValueError(f"n={n} not a multiple of bn={bn}")
    signed = signed_offsets(offsets, n)
    h_lo, h_hi = halo_extents(offsets, n)
    if max(h_lo, h_hi) > bn:
        raise ValueError(
            f"halo extents ({h_lo}, {h_hi}) exceed bn={bn}; widen the "
            f"row tile or use the full-stripe kernel")
    fused = _parse_kernel_comm(comm)
    ex = h_lo + bn + h_hi
    grid = (n // bn, d // bd)
    tile = pl.BlockSpec((bn, bd), lambda i, j, *_: (i, j))
    scratch = [pltpu.VMEM((ex, bd), y.dtype)]
    kw = dict(n=n, bn=bn, bd=bd, h_lo=h_lo, h_hi=h_hi,
              w_self=float(w_self), signed=signed,
              weights=tuple(float(c) for c in weights),
              laplacian=laplacian, prng=prng)
    if fused is None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2, 3))],
        )
        body = functools.partial(_circ_halo_body, levels=0.0, ef=False,
                                 fused=False, **kw)
        return pl.pallas_call(
            body, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, d), y.dtype),
            interpret=interpret)(y)
    bits, ef = fused
    if prng == "pltpu" and interpret:
        raise ValueError("prng='pltpu' needs compiled TPU lowering; "
                         "interpret mode uses prng='hash'")
    vec = pl.BlockSpec((n, 1), lambda i, j, *_: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [vec, vec, hbm] + ([hbm] if ef else [])
    out_shape = jax.ShapeDtypeStruct((n, d), y.dtype)
    if ef:
        out_specs = (tile, tile)
        out_shape = (out_shape, jax.ShapeDtypeStruct((n, d), jnp.float32))
        scratch.append(pltpu.VMEM((ex, bd), hat.dtype))
    else:
        out_specs = tile
    scratch.append(pltpu.VMEM((ex, bd), jnp.float32))   # materialized pay
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2, 3))])
    body = functools.partial(_circ_halo_body,
                             levels=float(2 ** bits - 1), ef=ef,
                             fused=True, **kw)
    operands = (seed.reshape(-1).astype(jnp.int32), zp, scale, y) \
        + ((hat,) if ef else ())
    return pl.pallas_call(
        body, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret)(*operands)


# ---------------------------------------------------------------------------
# Full-stripe sparse-gather kernel (plain + comm-fused)
# ---------------------------------------------------------------------------

def _sparse_body(idx_ref, wts_ref, wself_ref, y_ref, out_ref, *, k,
                 laplacian):
    """Per-row neighbor gather over one (n, bd) column stripe.

    idx_ref / wts_ref: flattened (n·k,) padded neighbor/weight tables,
    wself_ref: (n,) diagonal — all scalar-prefetched (SMEM), so the row
    loop can compute its gather addresses before touching VMEM.  Padding
    slots hold the row's own index with weight 0, so every dynamic slice
    is in-bounds and padded lanes contribute nothing.
    """
    n = y_ref.shape[0]

    def row_body(i, _):
        yi = y_ref[pl.ds(i, 1), :].astype(jnp.float32)
        acc0 = wself_ref[i] * yi

        def nb_body(j, acc):
            nb = idx_ref[i * k + j]
            w = wts_ref[i * k + j]
            return acc + w * y_ref[pl.ds(nb, 1), :].astype(jnp.float32)

        acc = jax.lax.fori_loop(0, k, nb_body, acc0)
        if laplacian:
            acc = yi - acc
        out_ref[pl.ds(i, 1), :] = acc.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n, row_body, 0)


def _sparse_fused_body(idx_ref, wts_ref, wself_ref, seed_ref, zp_ref,
                       scale_ref, *refs, k, laplacian, levels, ef, bd,
                       prng):
    """Fused sparse gather: the resident stripe's payload is quantized
    once into a VMEM scratch (all consumer rows see the same decoded
    broadcast), then the row loop gathers from the payload while the
    self term reads the exact y."""
    if ef:
        y_ref, hat_ref, out_ref, pay_ref, pay_scr = refs
    else:
        y_ref, out_ref, pay_scr = refs
    n = y_ref.shape[0]
    j = pl.program_id(0)
    y = y_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, bd), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, bd), 1) + j * bd
    u = _block_uniform(seed_ref[0], rows, cols, (n, bd), prng, pids=(j,))
    if ef:
        hat = hat_ref[...].astype(jnp.float32)
        pay = hat + _quantize(y - hat, zp_ref[...], scale_ref[...], u,
                              levels)
        pay_ref[...] = pay.astype(pay_ref.dtype)
    else:
        pay = _quantize(y, zp_ref[...], scale_ref[...], u, levels)
    pay_scr[...] = pay

    def row_body(i, _):
        yi = y_ref[pl.ds(i, 1), :].astype(jnp.float32)
        acc0 = wself_ref[i] * yi

        def nb_body(jj, acc):
            nb = idx_ref[i * k + jj]
            w = wts_ref[i * k + jj]
            return acc + w * pay_scr[pl.ds(nb, 1), :]

        acc = jax.lax.fori_loop(0, k, nb_body, acc0)
        if laplacian:
            acc = yi - acc
        out_ref[pl.ds(i, 1), :] = acc.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n, row_body, 0)


@functools.partial(jax.jit, static_argnames=("laplacian", "bd",
                                             "interpret", "comm",
                                             "prng"))
def sparse_mix_matvec(y: jnp.ndarray, w_self: jnp.ndarray,
                      neighbors: jnp.ndarray, weights: jnp.ndarray,
                      zp=None, scale=None, seed=None, hat=None, *,
                      laplacian: bool = False, bd: int = 128,
                      interpret: bool | None = None, comm: str | None = None,
                      prng: str = "hash"):
    """W·Y (or (I−W)·Y) for arbitrary sparse W; y: (n, d), d % bd == 0.

    w_self: (n,) diagonal of W; neighbors/weights: (n, k) padded
    fixed-degree tables (`topology.structure.SparseStructure`) — row i's
    unused slots hold index i with weight 0.  O(n·k·d) FMAs, one read +
    one write of the stripe like the circulant kernel, but the neighbor
    rows come from scalar-prefetch-addressed dynamic sublane slices
    instead of static cyclic shifts.

    `comm` lowering as in `circulant_mix_matvec`: gathered neighbor
    rows are replaced by their in-kernel quantizer roundtrip (per-row
    zp/scale operands + in-kernel uniforms), self term exact; ``+ef``
    additionally takes `hat` and returns (out, payload).
    """
    interpret = pallas_interpret(interpret)
    n, d = y.shape
    if d % bd:
        raise ValueError(f"d={d} not a multiple of bd={bd}")
    if neighbors.shape != weights.shape or neighbors.shape[0] != n:
        raise ValueError(
            f"neighbors/weights must both be (n, k); got "
            f"{neighbors.shape} / {weights.shape} with n={n}")
    k = neighbors.shape[1]
    idx_flat = neighbors.reshape(-1).astype(jnp.int32)
    wts_flat = weights.reshape(-1).astype(jnp.float32)
    wself = w_self.reshape(-1).astype(jnp.float32)
    fused = _parse_kernel_comm(comm)
    if fused is None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // bd,),
            in_specs=[pl.BlockSpec((n, bd), lambda j, *_: (0, j))],
            out_specs=pl.BlockSpec((n, bd), lambda j, *_: (0, j)),
        )
        body = functools.partial(_sparse_body, k=k, laplacian=laplacian)
        return pl.pallas_call(body, grid_spec=grid_spec,
                              out_shape=jax.ShapeDtypeStruct((n, d),
                                                             y.dtype),
                              interpret=interpret)(idx_flat, wts_flat,
                                                   wself, y)
    bits, ef = fused
    if prng == "pltpu" and interpret:
        raise ValueError("prng='pltpu' needs compiled TPU lowering; "
                         "interpret mode uses prng='hash'")
    stripe = pl.BlockSpec((n, bd), lambda j, *_: (0, j))
    vec = pl.BlockSpec((n, 1), lambda j, *_: (0, 0))
    in_specs = [vec, vec, stripe] + ([stripe] if ef else [])
    out_shape = jax.ShapeDtypeStruct((n, d), y.dtype)
    if ef:
        out_specs = (stripe, stripe)
        out_shape = (out_shape, jax.ShapeDtypeStruct((n, d), jnp.float32))
    else:
        out_specs = stripe
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(d // bd,),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)])
    body = functools.partial(_sparse_fused_body, k=k,
                             laplacian=laplacian,
                             levels=float(2 ** bits - 1), ef=ef, bd=bd,
                             prng=prng)
    operands = (idx_flat, wts_flat, wself,
                seed.reshape(-1).astype(jnp.int32), zp, scale, y) \
        + ((hat,) if ef else ())
    return pl.pallas_call(
        body, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret)(*operands)


# ---------------------------------------------------------------------------
# Row-tiled sparse-gather kernel (plain + non-EF comm-fused)
# ---------------------------------------------------------------------------

def _sparse_halo_body(*refs, k, bn, bd, laplacian, levels, fused, prng):
    if fused:
        (idx_ref, wts_ref, wself_ref, seed_ref, zp_ref, scale_ref,
         y_hbm, out_ref, own, nbuf, sem) = refs
    else:
        idx_ref, wts_ref, wself_ref, y_hbm, out_ref, own, nbuf, sem = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    row0 = i * bn
    col0 = j * bd
    blk = pltpu.make_async_copy(
        y_hbm.at[pl.ds(row0, bn), pl.ds(col0, bd)], own, sem.at[k])
    blk.start()
    blk.wait()

    def row_body(r, _):
        gi = row0 + r

        def mk(jj):
            nb = idx_ref[gi * k + jj]
            return pltpu.make_async_copy(
                y_hbm.at[pl.ds(nb, 1), pl.ds(col0, bd)],
                nbuf.at[pl.ds(jj, 1), :], sem.at[jj])

        def start_body(jj, _):
            mk(jj).start()
            return 0

        def wait_body(jj, _):
            mk(jj).wait()
            return 0

        jax.lax.fori_loop(0, k, start_body, 0)
        jax.lax.fori_loop(0, k, wait_body, 0)
        yi = own[pl.ds(r, 1), :].astype(jnp.float32)
        acc0 = wself_ref[gi] * yi

        def nb_body(jj, acc):
            nb = idx_ref[gi * k + jj]
            w = wts_ref[gi * k + jj]
            row = nbuf[pl.ds(jj, 1), :].astype(jnp.float32)
            if fused:
                rows = jnp.full((1, bd), nb, jnp.int32)
                cols = jax.lax.broadcasted_iota(jnp.int32, (1, bd), 1) \
                    + col0
                u = _block_uniform(seed_ref[0], rows, cols, (1, bd),
                                   prng, pids=(i, j))
                row = _quantize(row, zp_ref[pl.ds(nb, 1)],
                                scale_ref[pl.ds(nb, 1)], u, levels)
            return acc + w * row

        acc = jax.lax.fori_loop(0, k, nb_body, acc0)
        if laplacian:
            acc = yi - acc
        out_ref[pl.ds(r, 1), :] = acc.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, bn, row_body, 0)


@functools.partial(jax.jit, static_argnames=("laplacian", "bn", "bd",
                                             "interpret", "comm",
                                             "prng"))
def sparse_mix_matvec_halo(y: jnp.ndarray, w_self: jnp.ndarray,
                           neighbors: jnp.ndarray, weights: jnp.ndarray,
                           zp=None, scale=None, seed=None, *,
                           laplacian: bool = False, bn: int = 256,
                           bd: int = 128, interpret: bool | None = None,
                           comm: str | None = None, prng: str = "hash"):
    """Row-tiled twin of `sparse_mix_matvec`: grid (n/bn, d/bd), the
    operand stays in HBM; each program DMAs its own (bn, bd) row block
    once and each neighbor row (1, bd) on demand from the scalar-
    prefetched tables — per-program VMEM is O((bn + k)·bd) regardless
    of n.  Accumulation order matches the full-stripe kernel, so the
    variants agree bitwise (comm-fused included, via the position-keyed
    PRNG).  Error-feedback comm is not lowered here (the EF payload
    write-back needs the full stripe) — MixingOp falls back for it."""
    interpret = pallas_interpret(interpret)
    n, d = y.shape
    if d % bd:
        raise ValueError(f"d={d} not a multiple of bd={bd}")
    if n % bn:
        raise ValueError(f"n={n} not a multiple of bn={bn}")
    if neighbors.shape != weights.shape or neighbors.shape[0] != n:
        raise ValueError(
            f"neighbors/weights must both be (n, k); got "
            f"{neighbors.shape} / {weights.shape} with n={n}")
    k = neighbors.shape[1]
    idx_flat = neighbors.reshape(-1).astype(jnp.int32)
    wts_flat = weights.reshape(-1).astype(jnp.float32)
    wself = w_self.reshape(-1).astype(jnp.float32)
    fused = _parse_kernel_comm(comm)
    if fused is not None and fused[1]:
        raise ValueError("sparse halo kernel does not lower '+ef' comm; "
                         "use the full-stripe kernel or the XLA path")
    grid = (n // bn, d // bd)
    scratch = [pltpu.VMEM((bn, bd), y.dtype),
               pltpu.VMEM((max(k, 1), bd), y.dtype),
               pltpu.SemaphoreType.DMA((k + 1,))]
    out_spec = pl.BlockSpec((bn, bd), lambda i, j, *_: (i, j))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if fused is None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid,
            in_specs=[hbm], out_specs=out_spec,
            scratch_shapes=scratch)
        body = functools.partial(_sparse_halo_body, k=k, bn=bn, bd=bd,
                                 laplacian=laplacian, levels=0.0,
                                 fused=False, prng=prng)
        return pl.pallas_call(
            body, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, d), y.dtype),
            interpret=interpret)(idx_flat, wts_flat, wself, y)
    bits, _ = fused
    if prng == "pltpu" and interpret:
        raise ValueError("prng='pltpu' needs compiled TPU lowering; "
                         "interpret mode uses prng='hash'")
    vec = pl.BlockSpec((n, 1), lambda i, j, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=grid,
        in_specs=[vec, vec, hbm], out_specs=out_spec,
        scratch_shapes=scratch)
    body = functools.partial(_sparse_halo_body, k=k, bn=bn, bd=bd,
                             laplacian=laplacian,
                             levels=float(2 ** bits - 1), fused=True,
                             prng=prng)
    return pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), y.dtype),
        interpret=interpret)(idx_flat, wts_flat, wself,
                             seed.reshape(-1).astype(jnp.int32),
                             zp, scale, y)


# ---------------------------------------------------------------------------
# Fused DIHGP Neumann step (plain + non-EF comm-fused)
# ---------------------------------------------------------------------------

def _neumann_body(h_ref, hvp_ref, p_ref, dsc_ref, out_ref, *, w_self,
                  offsets, weights, beta):
    hy = h_ref[...]
    h = hy.astype(jnp.float32)
    mix = h * w_self
    for o, c in zip(offsets, weights):
        mix = mix + c * _shift(hy, o).astype(jnp.float32)
    dsc = dsc_ref[...].astype(jnp.float32)          # (n, 1) broadcasts
    num = dsc * h - (h - mix) - beta * hvp_ref[...].astype(jnp.float32) \
        - p_ref[...].astype(jnp.float32)
    out_ref[...] = (num / dsc).astype(out_ref.dtype)


def _neumann_fused_body(seed_ref, zp_ref, scale_ref, h_ref, hvp_ref,
                        p_ref, dsc_ref, out_ref, *, w_self, offsets,
                        weights, beta, levels, bd, prng):
    """Neumann step with the W·h gossip quantized in-kernel: the
    neighbor rows mix the decoded payload ĥ, the self/D̃/HVP/p terms
    (never on the wire) stay exact."""
    j = pl.program_id(0)
    h = h_ref[...].astype(jnp.float32)
    n = h.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, bd), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, bd), 1) + j * bd
    u = _block_uniform(seed_ref[0], rows, cols, (n, bd), prng, pids=(j,))
    pay = _quantize(h, zp_ref[...], scale_ref[...], u, levels)
    mix = h * w_self
    for o, c in zip(offsets, weights):
        mix = mix + c * _shift(pay, o)
    dsc = dsc_ref[...].astype(jnp.float32)
    num = dsc * h - (h - mix) - beta * hvp_ref[...].astype(jnp.float32) \
        - p_ref[...].astype(jnp.float32)
    out_ref[...] = (num / dsc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("w_self", "offsets",
                                             "weights", "beta", "bd",
                                             "interpret", "comm",
                                             "prng"))
def circulant_neumann_step(h: jnp.ndarray, hvp_h: jnp.ndarray,
                           p: jnp.ndarray, d_scalar: jnp.ndarray,
                           zp=None, scale=None, seed=None, *,
                           w_self: float, offsets: tuple[int, ...],
                           weights: tuple[float, ...], beta: float,
                           bd: int = 128, interpret: bool | None = None,
                           comm: str | None = None,
                           prng: str = "hash") -> jnp.ndarray:
    """One DIHGP Neumann iteration (Eq. 14), fused:

        h⁺ = (D̃h − (I−W)h − β·hvp_h − p) / D̃

    h, hvp_h, p: (n, d); d_scalar: (n, 1) per-agent D̃ diagonals.
    W·h is computed in-kernel from the circulant weights, so the whole
    update is a single pass over the operands.  With `comm` (non-EF
    int8/int4 + zp/scale/seed operands) the W·h gossip additionally
    runs the quantizer roundtrip in the same pass — the DIHGP hot loop
    keeps one traversal even under compressed gossip.
    """
    interpret = pallas_interpret(interpret)
    n, d = h.shape
    if d % bd:
        raise ValueError(f"d={d} not a multiple of bd={bd}")
    if d_scalar.shape != (n, 1):
        raise ValueError(f"d_scalar must be (n, 1), got {d_scalar.shape}")
    fused = _parse_kernel_comm(comm)
    if fused is None:
        stripe = pl.BlockSpec((n, bd), lambda j: (0, j))
        grid_spec = pl.GridSpec(
            grid=(d // bd,),
            in_specs=[stripe, stripe, stripe,
                      pl.BlockSpec((n, 1), lambda j: (0, 0))],
            out_specs=stripe,
        )
        body = functools.partial(_neumann_body, w_self=float(w_self),
                                 offsets=tuple(offsets),
                                 weights=tuple(float(c)
                                               for c in weights),
                                 beta=float(beta))
        return pl.pallas_call(body, grid_spec=grid_spec,
                              out_shape=jax.ShapeDtypeStruct((n, d),
                                                             h.dtype),
                              interpret=interpret)(h, hvp_h, p, d_scalar)
    bits, ef = fused
    if ef:
        raise ValueError("the fused Neumann kernel does not lower '+ef' "
                         "comm (no payload write-back); compose it from "
                         "mix_c + the XLA update instead")
    if prng == "pltpu" and interpret:
        raise ValueError("prng='pltpu' needs compiled TPU lowering; "
                         "interpret mode uses prng='hash'")
    stripe = pl.BlockSpec((n, bd), lambda j, *_: (0, j))
    vec = pl.BlockSpec((n, 1), lambda j, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(d // bd,),
        in_specs=[vec, vec, stripe, stripe, stripe, vec],
        out_specs=stripe)
    body = functools.partial(_neumann_fused_body, w_self=float(w_self),
                             offsets=tuple(offsets),
                             weights=tuple(float(c) for c in weights),
                             beta=float(beta),
                             levels=float(2 ** bits - 1), bd=bd,
                             prng=prng)
    return pl.pallas_call(body, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
                          interpret=interpret)(
        seed.reshape(-1).astype(jnp.int32), zp, scale, h, hvp_h, p,
        d_scalar)


@functools.partial(jax.jit, static_argnames=("w_self", "w_edge", "bn",
                                             "bd", "interpret"))
def ring_laplacian_matvec(y: jnp.ndarray, *, w_self: float, w_edge: float,
                          bn: int = 8, bd: int = 128,
                          interpret: bool | None = None) -> jnp.ndarray:
    """(I − W)·Y for ring W (compat wrapper over the circulant kernel);
    y: (n, d) with d % bd == 0.  `bn` is accepted for API compatibility
    but ignored: the column-stripe kernel no longer tiles the agent
    axis, so any n works."""
    n, d = y.shape
    if n == 2:
        # ±1 name the same neighbor on C_2 — one offset, else the edge
        # weight would be applied twice
        offsets, weights = (1,), (w_edge,)
    else:
        offsets, weights = (1, n - 1), (w_edge, w_edge)
    return circulant_mix_matvec(y, w_self=w_self, offsets=offsets,
                                weights=weights, laplacian=True,
                                bd=bd, interpret=interpret)
