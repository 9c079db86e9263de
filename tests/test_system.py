"""End-to-end system behaviour tests.

1. The full DAGM pipeline reproduces the paper's qualitative claims on a
   small instance (communication-efficient decentralized bilevel
   optimization that actually solves the original problem).
2. The training launcher runs an LM end to end (loss goes down).
3. The dry-run utilities produce sane specs without big compiles.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (DAGMConfig, dagm_run, dgtbo_run, make_network,
                        quadratic_bilevel)


@pytest.mark.slow
def test_paper_headline_end_to_end():
    """DAGM matches the matrix-shipping baseline's accuracy with far
    less communication — the paper's core claim, end to end."""
    n = 10
    net = make_network("erdos_renyi", n, r=0.5, seed=0)
    prob = quadratic_bilevel(n, 3, 5, seed=0, mu_f=0.4)

    cfg = DAGMConfig(alpha=0.05, beta=0.1, K=120, M=10, U=4)
    dagm = dagm_run(prob, net, cfg)
    dgtbo = dgtbo_run(prob, net, alpha=0.05, beta=0.1, K=120, M=10, N=4)

    hg_dagm = float(dagm.metrics["true_hypergrad_norm_sq"][-1])
    hg_dgtbo = float(dgtbo.metrics["true_hypergrad_norm_sq"][-1])
    assert hg_dagm < 2.0 * hg_dgtbo + 1e-5       # comparable accuracy

    d1, d2 = prob.d1, prob.d2
    dagm_floats = cfg.M * d2 + cfg.U * d2 + d1
    assert dagm_floats < dgtbo.comm_floats_per_round  # cheaper rounds


@pytest.mark.slow
def test_train_launcher_end_to_end(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "qwen3-4b", "--smoke", "--steps", "8",
               "--seq-len", "32", "--global-batch", "4",
               "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "4",
               "--log-every", "100"])
    assert rc == 0
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path / "ck")) == 8


def test_input_specs_all_combinations():
    """input_specs() yields shardable ShapeDtypeStructs for all 40
    (arch × shape) pairs without touching devices."""
    from repro.launch.dryrun import SKIP, input_specs
    from repro.configs import ARCHS, INPUT_SHAPES
    count = 0
    for arch in ARCHS:
        for shape in INPUT_SHAPES:
            if (arch, shape) in SKIP:
                continue
            specs = input_specs(arch, shape)
            for leaf in jax.tree.leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct)
            count += 1
    assert count == 39      # 40 minus the documented whisper long_500k


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes_from_hlo
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), dimensions={0}
  %ar.1 = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%sum
  %cp = (f32[2,2]{1,0}, f32[2,2]{1,0}) collective-permute-start(f32[2,2]{1,0} %z)
  %done = f32[2,2]{1,0} collective-permute-done((f32[2,2],f32[2,2]) %cp)
"""
    got = collective_bytes_from_hlo(hlo)
    assert got["all-gather"] == 8 * 128 * 2
    assert got["all-reduce"] == 256 * 4
    assert got["collective-permute"] == 2 * (2 * 2 * 4)


def test_chip_peaks_table():
    """One peak table keyed by device_kind; an unknown chip is an
    error, never a default."""
    from repro.launch.mesh import chip_peaks, roofline_terms
    peaks = chip_peaks("TPU v5 lite")
    assert peaks["hbm_bw"] == 819e9
    assert peaks["ici_bw"] == 1600e9 / 8          # 1,600 Gbit/s per chip
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v4")
    terms = roofline_terms(197e12, 2 * 819e9, 0.0)
    assert terms["compute_s"] == 1.0 and terms["memory_s"] == 2.0
    assert terms["bottleneck"] == "memory_s"


def test_compile_cache_placement(monkeypatch):
    """A JAX_COMPILATION_CACHE_DIR set from outside wins untouched;
    otherwise the cache sits at one fixed path in the checkout."""
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "outside-cache")
        assert compile_cache.enable_compile_cache() == "outside-cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CACHE_DIR)
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)



CACHE_KEY_SCRIPT = r'''
import os, sys
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
sys.path.insert(0, sys.argv[2])
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
from repro.compile_cache import enable_compile_cache
enable_compile_cache()
SRC = """
import jax, jax.numpy as jnp
def run(scope):
    @jax.jit
    def f(a):
        def body(i, c):
            with jax.named_scope(scope):
                return jnp.tanh(c @ c)
        return jax.lax.fori_loop(0, 2, body, a).sum()
    a = jnp.ones((8, 8))
    f(a).block_until_ready()
    return f.lower(a).compile().as_text()
"""
def entries():
    return len([f for f in os.listdir(sys.argv[1]) if f.startswith("jit_f")])
def deeper(ns):
    return ns["run"]("gossip")
# the same source at two paths, called from two depths: one entry
for path in ("/a/checkout/k.py", "/b/elsewhere/deeper/k.py"):
    ns = {}
    exec(compile(SRC, path, "exec"), ns)
    ns["run"]("gossip") if path.startswith("/a") else deeper(ns)
print("SAME", entries())
hlo = ns["run"]("dihgp")    # another scope name: another entry
print("SCOPED", entries())
# the compiled ops keep their whole scope path (a device trace reads it)
print("OPNAME", 'op_name="jit(f)/while/body/closed_call/dihgp/' in hlo)
'''


def test_compile_cache_key_follows_scopes_not_paths(tmp_path):
    """The persistent cache keys on the ops' named scopes (an executable
    keeps the metadata it was compiled with) but not on where the
    checkout lies or from where the program is called."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", CACHE_KEY_SCRIPT, str(tmp_path),
         os.path.abspath(src)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SAME 1" in out.stdout and "SCOPED 2" in out.stdout, out.stdout
    assert "OPNAME True" in out.stdout, out.stdout
