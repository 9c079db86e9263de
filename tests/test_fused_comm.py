"""Parity suite for the comm-fused / halo-tiled Pallas mixing kernels.

What is locked down here (ISSUE 7):
  * `comm="identity"` never engages the fused lowering — the plain
    kernel runs and the MixingOp `*_c` identity path stays bitwise
    equal to the uncompressed `_apply`.
  * int8/int4 fused gossip matches the `Compressor.roundtrip` + mix
    XLA reference within quantization tolerance (the two paths share
    `row_quant_params` metadata and differ only in their uniforms).
  * The in-kernel per-row quantizer is unbiased (hypothesis property
    over the hash-counter PRNG).
  * Row-tiled halo kernels agree with the full-stripe kernels across
    `bn` choices — bitwise on the plain path, payload-bitwise plus
    ≤ 1-ulp output tolerance on the fused path (FMA re-association).
  * n = 4096 (full stripe over the VMEM budget) auto-switches to the
    halo tier and stays correct.
  * Fallbacks warn once per op/shape and never raise; `pallas_mode`
    restores state; the platform decides interpret mode.
"""
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.comm import channel_init, parse_comm_spec, row_quant_params
from repro.kernels import mixing_matvec as mk
from repro.kernels import ops as kops
from repro.kernels import pallas_mode
from repro.topology import make_network
from repro.topology.ops import MixingOp, make_mixing_op

KEY = jax.random.PRNGKey(0)


def _y(n, d, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d),
                             jnp.float32)


def _circ(n=16, offsets=(1, 2)):
    return make_network("circulant", n, offsets=offsets)


# ---------------------------------------------------------------------------
# Kernel-level parity
# ---------------------------------------------------------------------------

def test_comm_identity_is_the_unfused_kernel():
    y = _y(16, 256)
    net = _circ()
    op = make_mixing_op(net, backend="circulant")
    s = op.structure
    kw = dict(w_self=s.w_self, offsets=s.offsets, weights=s.weights,
              laplacian=False)
    plain = mk.circulant_mix_matvec(y, **kw)
    ident = mk.circulant_mix_matvec(y, comm="identity", **kw)
    assert np.array_equal(np.asarray(plain), np.asarray(ident))


@pytest.mark.parametrize("comm,bits", [("int8", 8), ("int4", 4)])
def test_fused_matches_roundtrip_mix_within_quant_tolerance(comm, bits):
    """Fused kernel vs XLA roundtrip+mix: both quantize the payload
    with the same (zp, scale); their decoded codes differ by at most
    one level per element, so the mixed outputs differ by at most
    Σ|c_o|·scale (the self term is exact on both paths)."""
    n, d = 16, 256
    y = _y(n, d)
    net = _circ()
    op = make_mixing_op(net, backend="circulant")
    s = op.structure
    zp, scale = row_quant_params(y, bits)
    seed = jnp.asarray([77], jnp.int32)
    fused = mk.circulant_mix_matvec(y, zp, scale, seed, w_self=s.w_self,
                                    offsets=s.offsets, weights=s.weights,
                                    laplacian=False, comm=comm)
    comp = parse_comm_spec(comm).compressor
    pay = comp.roundtrip(y, jax.random.PRNGKey(3))
    ref = float(s.w_self) * y
    for o, c in zip(s.offsets, s.weights):
        ref = ref + c * jnp.roll(pay, -o, axis=0)
    tol = float(sum(abs(c) for c in s.weights) * jnp.max(scale)) + 1e-6
    assert float(jnp.abs(fused - ref).max()) <= tol
    # and the fused path is exact where the payload happens to agree
    assert fused.shape == ref.shape and fused.dtype == ref.dtype


def test_fused_ef_payload_matches_choco_protocol():
    """EF fused kernel returns payload = hat + C(y − hat) computed from
    the same (zp, scale) metadata the wire would carry."""
    n, d = 16, 256
    y = _y(n, d)
    hat = 0.5 * _y(n, d, seed=9)
    src = y - hat
    zp, scale = row_quant_params(src, 8)
    seed = jnp.asarray([5], jnp.int32)
    net = _circ()
    s = make_mixing_op(net, backend="circulant").structure
    out, pay = mk.circulant_mix_matvec(y, zp, scale, seed, hat,
                                       w_self=s.w_self, offsets=s.offsets,
                                       weights=s.weights, laplacian=False,
                                       comm="int8+ef")
    # the decoded innovation is a valid quantizer output: on the zp +
    # k·scale grid per row, within one level of the true residual
    q = (pay - hat - zp) / scale
    assert float(jnp.abs(q - jnp.round(q)).max()) < 1e-3
    assert float(jnp.abs((pay - hat) - src).max()) \
        <= float(jnp.max(scale)) + 1e-6
    # out mixes the payload with the self term exact
    ref = float(s.w_self) * y
    for o, c in zip(s.offsets, s.weights):
        ref = ref + c * jnp.roll(pay, -o, axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bn", [8, 16, 32])
def test_halo_plain_bitwise_equals_full_stripe(bn):
    y = _y(32, 256, seed=4)
    net = _circ(32, offsets=(1, 2, 3))
    s = make_mixing_op(net, backend="circulant").structure
    kw = dict(w_self=s.w_self, offsets=s.offsets, weights=s.weights)
    for lap in (False, True):
        full = mk.circulant_mix_matvec(y, laplacian=lap, **kw)
        halo = mk.circulant_mix_matvec_halo(y, laplacian=lap, bn=bn, **kw)
        assert np.array_equal(np.asarray(full), np.asarray(halo))


@pytest.mark.parametrize("bn", [8, 16, 32])
def test_halo_fused_payload_bitwise_output_one_ulp(bn):
    """The position-keyed counter PRNG gives every tiling the same
    stochastic draws: the EF payload is bitwise identical, the mixed
    output agrees to ≤ 1 ulp (compiler FMA re-association)."""
    n, d = 32, 256
    y = _y(n, d, seed=4)
    net = _circ(n, offsets=(1, 2, 3))
    s = make_mixing_op(net, backend="circulant").structure
    seed = jnp.asarray([11], jnp.int32)
    kw = dict(w_self=s.w_self, offsets=s.offsets, weights=s.weights,
              laplacian=True, comm="int8")
    zp, scale = row_quant_params(y, 8)
    full = mk.circulant_mix_matvec(y, zp, scale, seed, **kw)
    halo = mk.circulant_mix_matvec_halo(y, zp, scale, seed, bn=bn, **kw)
    np.testing.assert_allclose(np.asarray(full), np.asarray(halo),
                               atol=2e-6, rtol=0)
    # EF: payload itself is bitwise reproducible across tilings
    hat = 0.25 * _y(n, d, seed=6)
    zp2, sc2 = row_quant_params(y - hat, 8)
    kw["comm"] = "int8+ef"
    kw["laplacian"] = False
    _, pay_f = mk.circulant_mix_matvec(y, zp2, sc2, seed, hat, **kw)
    _, pay_h = mk.circulant_mix_matvec_halo(y, zp2, sc2, seed, hat,
                                            bn=bn, **kw)
    assert np.array_equal(np.asarray(pay_f), np.asarray(pay_h))


@pytest.mark.parametrize("bn", [8, 16])
def test_sparse_halo_agrees_with_full_stripe(bn):
    n, d, k = 16, 256, 3
    y = _y(n, d, seed=2)
    nb = np.stack([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n,
                   (np.arange(n) - 1) % n], axis=1).astype(np.int32)
    wts = np.tile(np.asarray([[0.2, 0.1, 0.2]], np.float32), (n, 1))
    wself = jnp.full((n,), 0.5, jnp.float32)
    nb, wts = jnp.asarray(nb), jnp.asarray(wts)
    full = mk.sparse_mix_matvec(y, wself, nb, wts, laplacian=True)
    halo = mk.sparse_mix_matvec_halo(y, wself, nb, wts, laplacian=True,
                                     bn=bn)
    assert np.array_equal(np.asarray(full), np.asarray(halo))
    zp, scale = row_quant_params(y, 8)
    seed = jnp.asarray([3], jnp.int32)
    fullf = mk.sparse_mix_matvec(y, wself, nb, wts, zp, scale, seed,
                                 laplacian=False, comm="int8")
    halof = mk.sparse_mix_matvec_halo(y, wself, nb, wts, zp, scale, seed,
                                      laplacian=False, bn=bn, comm="int8")
    np.testing.assert_allclose(np.asarray(fullf), np.asarray(halof),
                               atol=2e-6, rtol=0)


def test_sparse_halo_rejects_ef():
    y = _y(8, 128)
    nb = jnp.zeros((8, 1), jnp.int32)
    wts = jnp.zeros((8, 1), jnp.float32)
    with pytest.raises(ValueError, match="ef"):
        mk.sparse_mix_matvec_halo(y, jnp.ones((8,)), nb, wts,
                                  jnp.zeros((8, 1)), jnp.ones((8, 1)),
                                  jnp.asarray([1], jnp.int32), bn=8,
                                  comm="int8+ef")


def test_fused_neumann_comm_matches_compose():
    n, d = 16, 256
    h, hvp, p = _y(n, d), 0.1 * _y(n, d, 1), 0.2 * _y(n, d, 2)
    dsc = 1.5 * jnp.ones((n, 1), jnp.float32)
    net = _circ()
    s = make_mixing_op(net, backend="circulant").structure
    zp, scale = row_quant_params(h, 8)
    seed = jnp.asarray([21], jnp.int32)
    out = mk.circulant_neumann_step(h, hvp, p, dsc, zp, scale, seed,
                                    w_self=s.w_self, offsets=s.offsets,
                                    weights=s.weights, beta=0.3,
                                    comm="int8")
    mixed = mk.circulant_mix_matvec(h, zp, scale, seed, w_self=s.w_self,
                                    offsets=s.offsets, weights=s.weights,
                                    laplacian=False, comm="int8")
    ref = (dsc * h - (h - mixed) - 0.3 * hvp - p) / dsc
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# In-kernel quantizer unbiasedness (hypothesis over the counter PRNG)
# ---------------------------------------------------------------------------

def test_hash_uniform_is_uniform():
    rows = jax.lax.broadcasted_iota(jnp.int32, (256, 512), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (256, 512), 1)
    u = mk._hash_uniform(jnp.int32(13), rows, cols)
    assert 0.0 <= float(u.min()) and float(u.max()) < 1.0
    assert abs(float(u.mean()) - 0.5) < 5e-3
    # distinct seeds decorrelate
    u2 = mk._hash_uniform(jnp.int32(14), rows, cols)
    corr = float(jnp.corrcoef(u.ravel(), u2.ravel())[0, 1])
    assert abs(corr) < 0.02


def test_in_kernel_quantizer_unbiased():
    hypothesis = pytest.importorskip("hypothesis")
    given, settings = hypothesis.given, hypothesis.settings
    st = hypothesis.strategies

    @given(data_seed=st.integers(0, 2 ** 16),
           bits=st.sampled_from([4, 8]))
    @settings(max_examples=10, deadline=None)
    def check(data_seed, bits):
        x = 3.0 * jax.random.normal(jax.random.PRNGKey(data_seed),
                                    (4, 64), jnp.float32)
        zp, scale = row_quant_params(x, bits)
        rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        levels = float(2 ** bits - 1)

        def one(seed):
            u = mk._hash_uniform(seed, rows, cols)
            return mk._quantize(x, zp, scale, u, levels)
        seeds = jnp.arange(400, dtype=jnp.int32) * 7919 + 3
        mean = jnp.mean(jax.vmap(one)(seeds), axis=0)
        # E[decode] = x up to metadata rounding; MC error ~ scale/√N
        tol = float(jnp.max(scale)) * (4.0 / np.sqrt(400)) \
            + float(jnp.max(scale)) * 2.0 ** -7 + 1e-5
        assert float(jnp.abs(mean - x).max()) <= tol

    check()


# ---------------------------------------------------------------------------
# MixingOp dispatch
# ---------------------------------------------------------------------------

def test_mixingop_identity_comm_never_fuses_bitwise():
    net = _circ()
    y = _y(16, 256)
    with pallas_mode(True):
        op = make_mixing_op(net, comm="identity")
        st = channel_init(op.comm, "x", y, KEY)
        out_c, st2 = op.mix_c(y, st)
        assert op._fused_plan(y) is None
        assert np.array_equal(np.asarray(out_c), np.asarray(op.mix(y)))
        assert int(st2.sends) == 1


@pytest.mark.parametrize("spec", ["int8", "int4", "int8+ef"])
def test_mixingop_fused_state_protocol_matches_xla(spec):
    """The fused path advances ChannelState exactly as
    `compressed_payload` does: same key split, same send count, hat
    replaced by the payload under EF."""
    net = _circ()
    y = _y(16, 256)
    op_x = make_mixing_op(net, comm=spec)            # XLA compose path
    st0 = channel_init(op_x.comm, "x", y, KEY)
    out_x, st_x = op_x.laplacian_c(y, st0)
    with pallas_mode(True):
        op_p = make_mixing_op(net, comm=spec)
        assert op_p._fused_plan(y.reshape(16, -1)) is not None
        out_p, st_p = op_p.laplacian_c(y, st0)
    assert np.array_equal(np.asarray(st_x.key), np.asarray(st_p.key))
    assert int(st_x.sends) == int(st_p.sends) == 1
    bits = op_x.comm.compressor.bits
    _, scale = row_quant_params(
        y - (st0.hat if op_x.comm.ef else 0.0), bits)
    tol = 2.0 * float(jnp.max(scale)) + 1e-6
    assert float(jnp.abs(out_p - out_x).max()) <= tol
    if op_x.comm.ef:
        # both hats are valid payloads on the shared quantizer grid
        assert st_p.hat.shape == st_x.hat.shape
        assert float(jnp.abs(st_p.hat - st_x.hat).max()) <= tol


def test_mixingop_nonfusable_policies_keep_xla_path():
    net = _circ()
    y = _y(16, 256)
    with pallas_mode(True):
        for spec in ("bf16", "top_k:0.25", "rand_k:0.25+ef"):
            op = make_mixing_op(net, comm=spec)
            assert not op.comm.fusable
            assert op._fused_plan(y) is None
        # bf16 *storage* also blocks fusion
        op = make_mixing_op(net, comm="int8", dtype="bf16")
        assert op._fused_plan(y) is None
        # masked views never fuse
        opm = make_mixing_op(net, comm="int8")
        mask = jnp.ones_like(opm.sparse.weights)
        assert opm.masked(mask)._fused_plan(y) is None


def test_auto_halo_switch_at_4096():
    """Full stripe at n=4096 exceeds VMEM_BUDGET_BYTES; the dispatch
    runs the halo kernel and stays correct vs the XLA circulant."""
    assert mk.stripe_vmem_bytes(4096) > mk.VMEM_BUDGET_BYTES
    net = make_network("circulant", 4096, offsets=(1, 2))
    y = _y(4096, 128, seed=8)
    xla = make_mixing_op(net, backend="circulant")
    with pallas_mode(True):
        op = make_mixing_op(net, comm="int8")
        tier, bn = op._stripe_plan(y, blocks=3, circulant=True)
        assert tier == "halo" and bn is not None and 4096 % bn == 0
        np.testing.assert_allclose(np.asarray(op.mix(y)),
                                   np.asarray(xla.mix(y)),
                                   atol=1e-5, rtol=1e-5)
        st = channel_init(op.comm, "x", y, KEY)
        out, st2 = op.mix_c(y, st)
        assert int(st2.sends) == 1
        _, scale = row_quant_params(y, 8)
        tol = 2.0 * float(jnp.max(scale)) + 1e-6
        assert float(jnp.abs(out - xla.mix(y)).max()) <= tol


def test_fallback_warns_once_per_shape():
    net = _circ()
    op = MixingOp(net.W, backend="circulant_pallas",
                  name="fused-warn-probe")
    bad = jnp.ones((16, 100), jnp.float32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        op.mix(bad)
        first = [x for x in w if issubclass(x.category, RuntimeWarning)]
    assert len(first) == 1 and "fused-warn-probe" in str(first[0].message)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        op.mix(bad)
        again = [x for x in w if issubclass(x.category, RuntimeWarning)]
    assert len(again) == 0


# ---------------------------------------------------------------------------
# pallas_mode / interpret rule
# ---------------------------------------------------------------------------

def test_pallas_mode_restores_state():
    before = kops.pallas_enabled()
    with pallas_mode(True):
        assert kops.pallas_enabled() == (True, True)
        with pallas_mode(False):
            assert kops.pallas_enabled()[0] is False
        assert kops.pallas_enabled() == (True, True)
    assert kops.pallas_enabled() == before
    with pytest.raises(RuntimeError):
        with pallas_mode(True):
            assert kops.pallas_enabled()[0] is True
            raise RuntimeError("boom")
    assert kops.pallas_enabled() == before


def test_env_override_interpret(monkeypatch):
    """The platform decides interpret mode: interpreted on the CPU,
    compiled on a TPU; an explicit `interpret` argument wins over the
    platform."""
    assert jax.default_backend() == "cpu"
    with pallas_mode(True):
        assert kops.pallas_interpret() is True
        assert kops.pallas_enabled() == (True, True)
        assert kops.pallas_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pallas_mode(True):
        assert kops.pallas_interpret() is False
        assert kops.pallas_enabled() == (True, False)
        assert kops.pallas_interpret(True) is True
