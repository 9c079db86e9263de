"""Compile rehearsal for a TPU v5e: the main path's kernels and the
sharded DAGM step, compiled for a described (not attached) `v5e:2x2`
topology with `interpret=False`.

Nothing runs: each case asserts that the chip's compiler accepts the
program, and the kernel cases that a Mosaic kernel (`tpu_custom_call`)
is in it.  The topology is described inside module-scoped fixtures,
never at import, and every case compiles in this test's own process
(only one process may hold the TPU compiler library at a time).
"""
from __future__ import annotations

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels import mixing_matvec as mk

W_SELF, W_EDGE = 1.0 / 3.0, 1.0 / 3.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # compiler logs stay off the filesystem; a compile for a described
    # chip can be written to a persistent cache but never read back, so
    # keep the cache off for this module
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _ring(n):
    return dict(w_self=W_SELF, offsets=(1, n - 1), weights=(W_EDGE, W_EDGE))


def _quant_args(sds, n, d, ef):
    args = [sds((n, d)), sds((n, 1)), sds((n, 1)), sds((1,), jnp.int32)]
    return args + ([sds((n, d))] if ef else [])


@pytest.fixture(scope="module")
def sds(one_chip):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_circulant_full_stripe_plain(sds, dtype):
    n, d = 64, 8192
    _compile_kernel(lambda y: mk.circulant_mix_matvec(
        y, **_ring(n), laplacian=True, interpret=False), sds((n, d), dtype))


@pytest.mark.parametrize("prng", ["hash", "pltpu"])
def test_circulant_full_stripe_int8_ef(sds, prng):
    n, d = 64, 8192
    _compile_kernel(lambda y, zp, sc, seed, hat: mk.circulant_mix_matvec(
        y, zp, sc, seed, hat, **_ring(n), comm="int8+ef", prng=prng,
        interpret=False), *_quant_args(sds, n, d, ef=True))


@pytest.mark.parametrize("comm", [None, "int8+ef"], ids=["plain", "int8+ef"])
def test_circulant_halo(sds, comm):
    n, d = 8192, 128
    ef = comm is not None
    bn = mk.pick_halo_bn(n, h_lo=1, h_hi=1, blocks=6 if ef else 3)
    assert bn is not None and n * 128 * 4 * 3 > mk.VMEM_BUDGET_BYTES
    if ef:
        fn = lambda y, zp, sc, seed, hat: mk.circulant_mix_matvec_halo(
            y, zp, sc, seed, hat, **_ring(n), bn=bn, comm=comm,
            interpret=False)
        args = _quant_args(sds, n, d, ef=True)
    else:
        fn = lambda y: mk.circulant_mix_matvec_halo(
            y, **_ring(n), bn=bn, laplacian=True, interpret=False)
        args = [sds((n, d))]
    _compile_kernel(fn, *args)


def _tables(sds, n, k):
    return sds((n,)), sds((n, k), jnp.int32), sds((n, k))


@pytest.mark.parametrize("comm", [None, "int8+ef"], ids=["plain", "int8+ef"])
def test_sparse_gather(sds, comm):
    n, d, k = 64, 8192, 12
    if comm is None:
        fn = lambda y, ws, idx, wts: mk.sparse_mix_matvec(
            y, ws, idx, wts, laplacian=True, interpret=False)
        args = [sds((n, d)), *_tables(sds, n, k)]
    else:
        fn = lambda y, ws, idx, wts, zp, sc, seed, hat: mk.sparse_mix_matvec(
            y, ws, idx, wts, zp, sc, seed, hat, comm=comm, interpret=False)
        y, zp, sc, seed, hat = _quant_args(sds, n, d, ef=True)
        args = [y, *_tables(sds, n, k), zp, sc, seed, hat]
    _compile_kernel(fn, *args)


def test_sparse_halo(sds):
    n, d, k = 8192, 128, 2
    _compile_kernel(lambda y, ws, idx, wts: mk.sparse_mix_matvec_halo(
        y, ws, idx, wts, laplacian=True, bn=2048, interpret=False),
        sds((n, d)), *_tables(sds, n, k))


@pytest.mark.parametrize("comm", [None, "int8"], ids=["plain", "int8"])
def test_neumann_step(sds, comm):
    n, d = 64, 8192
    hs = [sds((n, d)), sds((n, d)), sds((n, d)), sds((n, 1))]
    if comm is None:
        fn = lambda h, hv, p, dsc: mk.circulant_neumann_step(
            h, hv, p, dsc, **_ring(n), beta=0.1, interpret=False)
        args = hs
    else:
        fn = lambda h, hv, p, dsc, zp, sc, seed: mk.circulant_neumann_step(
            h, hv, p, dsc, zp, sc, seed, **_ring(n), beta=0.1, comm=comm,
            interpret=False)
        args = hs + [sds((n, 1)), sds((n, 1)), sds((1,), jnp.int32)]
    _compile_kernel(fn, *args)


@pytest.mark.parametrize("comm", ["identity", "int8+ef"])
def test_sharded_dagm_step(topo, comm):
    """One agent per chip of the 2x2 host: ring gossip compiles to
    collective-permutes."""
    from repro.core import quadratic_bilevel
    from repro.distributed.dagm_sharded import make_sharded_dagm
    from repro.solve import sharded_spec
    n, d1, d2 = 4, 128, 4096
    mesh = Mesh(np.array(topo.devices).reshape(n), ("data",))
    prob = quadratic_bilevel(n, d1, 8, seed=0)     # only g/f are used
    spec = sharded_spec(alpha=0.05, beta=0.1, M=2, U=2, curvature=5.5,
                        comm=comm)
    step, _ = make_sharded_dagm(prob.g, prob.f, spec, mesh)
    agent = NamedSharding(mesh, P("data"))
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=agent)
    batch = {"A": sds((n, d2, d2)), "P": sds((n, d2, d1)),
             "b": sds((n, d2)), "c": sds((n, d2))}
    args = [sds((n, d1)), sds((n, d2)), batch]
    if comm != "identity":
        args.append(jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=NamedSharding(mesh, P())))
    hlo = step.lower(*args).compile().as_text()
    assert "collective-permute" in hlo
