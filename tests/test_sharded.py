"""Sharded decentralized runtime: ring collectives + shard_map DAGM.

These need >1 device, which jax only grants via XLA_FLAGS at process
start — so the heavy checks run in a subprocess with
--xla_force_host_platform_device_count=8 and this module asserts on its
output.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow       # subprocess-spawning system tests

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import quadratic_bilevel, DAGMConfig, dagm_run
from repro.core.mixing import mix_apply
from repro.distributed.collectives import RingWeights, ring_mix
from repro.distributed.dagm_sharded import (ShardedDAGMConfig,
                                            make_sharded_dagm)

n = 8
mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
w = RingWeights.metropolis_ring(n)
net = w.to_network()

# --- 1. ring_mix == dense W mixing ---
z = jax.random.normal(jax.random.PRNGKey(0), (n, 5))
def local(zz):
    return jax.tree.map(lambda a: a[None], ring_mix(
        jax.tree.map(lambda a: a[0], zz), "data", w))
mixed = jax.jit(shard_map(local, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))(z)
dense = mix_apply(net.W_jnp(), z)
err1 = float(jnp.abs(mixed - dense).max())
print("RINGMIX_ERR", err1)

# --- 2. sharded DAGM ~ reference DAGM on the same ring ---
prob = quadratic_bilevel(n, 3, 4, seed=0)
curv = float(max(np.linalg.eigvalsh(np.asarray(prob.data["A"][i])).max()
                 for i in range(n)))
cfg = ShardedDAGMConfig(alpha=0.05, beta=0.1, M=10, U=5, curvature=curv)
step, _ = make_sharded_dagm(lambda x, y, b: prob.g(x, y, b),
                            lambda x, y, b: prob.f(x, y, b), cfg, mesh)
x = jnp.zeros((n, 3))
y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, 4))
y = y0
for _ in range(15):
    x, y, m = step(x, y, prob.data)

rcfg = DAGMConfig(alpha=0.05, beta=0.1, K=15, M=10, U=5,
                  dihgp="matrix_free", curvature=curv)
res = dagm_run(prob, net, rcfg, x0=jnp.zeros((n, 3)), y0=y0)
err2 = float(jnp.abs(res.x - x).max())
print("DAGM_ERR", err2)
print("OUTER", float(m["outer_loss"]))
"""


def test_sharded_matches_reference(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = {}
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2:
            vals[parts[0]] = float(parts[1])
    assert vals["RINGMIX_ERR"] < 1e-6
    assert vals["DAGM_ERR"] < 1e-4
    assert np.isfinite(vals["OUTER"])


SCRIPT_VARIANTS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import quadratic_bilevel
from repro.distributed.collectives import RingWeights, ring_mix
from repro.distributed.dagm_sharded import (ShardedDAGMConfig,
                                            make_sharded_dagm)

n = 8
mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
w = RingWeights.metropolis_ring(n)

# --- 1. bf16 gossip stays close to f32 gossip ---
z = jax.random.normal(jax.random.PRNGKey(0), (n, 64))
def local(zz, cd):
    return jax.tree.map(lambda a: a[None], ring_mix(
        jax.tree.map(lambda a: a[0], zz), "data", w, cd))
f32 = jax.jit(shard_map(lambda zz: local(zz, None), mesh=mesh,
                            in_specs=P("data"), out_specs=P("data"),
                            check_vma=False))(z)
b16 = jax.jit(shard_map(lambda zz: local(zz, jnp.bfloat16), mesh=mesh,
                            in_specs=P("data"), out_specs=P("data"),
                            check_vma=False))(z)
print("BF16_ERR", float(jnp.abs(f32 - b16).max()))

# --- 2. mix_every=M disables inner gossip; local steps still move y ---
prob = quadratic_bilevel(n, 3, 4, seed=0)
curv = float(max(np.linalg.eigvalsh(np.asarray(prob.data["A"][i])).max()
                 for i in range(n)))
for me in (1, 2):
    cfg = ShardedDAGMConfig(alpha=0.05, beta=0.1, M=4, U=3,
                            curvature=curv, mix_every=me)
    step, _ = make_sharded_dagm(lambda x, y, b: prob.g(x, y, b),
                                lambda x, y, b: prob.f(x, y, b), cfg, mesh)
    x = jnp.zeros((n, 3))
    y = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, 4))
    for _ in range(10):
        x, y, m = step(x, y, prob.data)
    print("MIXEVERY%d_OUTER" % me, float(m["outer_loss"]))
    print("MIXEVERY%d_HG" % me, float(m["hypergrad_norm"]))

# --- 3. unroll_loops == fori_loop version ---
cfgU = ShardedDAGMConfig(alpha=0.05, beta=0.1, M=4, U=3,
                         curvature=curv, unroll_loops=True)
cfgL = ShardedDAGMConfig(alpha=0.05, beta=0.1, M=4, U=3, curvature=curv)
xs, ys_ = [], []
for cfg in (cfgU, cfgL):
    step, _ = make_sharded_dagm(lambda x, y, b: prob.g(x, y, b),
                                lambda x, y, b: prob.f(x, y, b), cfg, mesh)
    x = jnp.zeros((n, 3))
    y = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, 4))
    for _ in range(5):
        x, y, m = step(x, y, prob.data)
    xs.append(np.asarray(x))
print("UNROLL_ERR", float(np.abs(xs[0] - xs[1]).max()))
"""


def test_dagm_variants(tmp_path):
    """bf16 gossip, local updates, unrolled accounting (§Perf-3)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT_VARIANTS.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = {}
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2:
            vals[parts[0]] = float(parts[1])
    assert vals["BF16_ERR"] < 0.02           # bf16 rounding only
    for me in (1, 2):
        assert np.isfinite(vals[f"MIXEVERY{me}_OUTER"])
        assert np.isfinite(vals[f"MIXEVERY{me}_HG"])
    assert vals["UNROLL_ERR"] < 1e-5         # unroll == fori_loop


SCRIPT_COMM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm import channel_init, parse_comm_spec
from repro.core import quadratic_bilevel
from jax import shard_map
from repro.distributed.collectives import RingWeights, ring_mix, ring_mix_c
from repro.distributed.dagm_sharded import (ShardedDAGMConfig,
                                            make_sharded_dagm,
                                            sharded_comm_ledger)

n = 8
mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
w = RingWeights.metropolis_ring(n)

# --- 1. identity ring_mix_c == ring_mix bit-for-bit; EF channel mixes
#        the decoded payload with the exact self term ---
z = jax.random.normal(jax.random.PRNGKey(0), (n, 64))
def mk(policy_spec):
    pol = parse_comm_spec(policy_spec)
    def local(zz, key):
        zz = jax.tree.map(lambda a: a[0], zz)
        st = channel_init(pol, "ch", zz, key)
        out, st = ring_mix_c(zz, "data", w, pol, st)
        return jax.tree.map(lambda a: a[None], out), st.sends
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P("data"), P()),
                             out_specs=(P("data"), P()),
                             check_vma=False))
ident, sends = mk("identity")(z, jax.random.PRNGKey(1))
plain = jax.jit(shard_map(
    lambda zz: jax.tree.map(lambda a: a[None], ring_mix(
        jax.tree.map(lambda a: a[0], zz), "data", w)),
    mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    check_vma=False))(z)
print("IDENT_BITMATCH", int(np.array_equal(np.asarray(ident),
                                           np.asarray(plain))))
q8, _ = mk("int8+ef")(z, jax.random.PRNGKey(1))
print("INT8_MIX_ERR", float(jnp.abs(q8 - plain).max()))

# --- 2. stochastic policies drive the 4-arg step; trajectories track
#        the identity run; comm_sends matches the static ledger ---
prob = quadratic_bilevel(n, 3, 4, seed=0)
curv = float(max(np.linalg.eigvalsh(np.asarray(prob.data["A"][i])).max()
                 for i in range(n)))
y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, 4))
outs = {{}}
for spec in ("identity", "int8+ef", "top_k:0.5+ef", "rand_k:0.5+ef"):
    cfg = ShardedDAGMConfig(alpha=0.05, beta=0.1, M=4, U=3,
                            curvature=curv, comm=spec, mix_every=2)
    step, _ = make_sharded_dagm(lambda x, y, b: prob.g(x, y, b),
                                lambda x, y, b: prob.f(x, y, b), cfg, mesh)
    x, y = jnp.zeros((n, 3)), y0
    for r in range(10):
        if cfg.comm_policy.stochastic:
            x, y, m = step(x, y, prob.data, jax.random.PRNGKey(r))
        else:
            x, y, m = step(x, y, prob.data)
    outs[spec] = np.asarray(x)
    led = sharded_comm_ledger(cfg, x[0], y[0], rounds=1)
    print("SENDS_MATCH_" + spec.replace(":", "").replace("+", ""),
          int(float(m["comm_sends"]) == led.total_sends()))
for spec in ("int8+ef", "top_k:0.5+ef", "rand_k:0.5+ef"):
    print("XERR_" + spec.replace(":", "").replace("+", ""),
          float(np.abs(outs[spec] - outs["identity"]).max()))
"""


def test_sharded_compressed_gossip(tmp_path):
    """repro.comm on the sharded tier: identity bit-match, EF channel
    algebra under shard_map, the stochastic 4-arg step, and
    sharded_comm_ledger vs the traced comm_sends metric."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT_COMM.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = {}
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2:
            vals[parts[0]] = float(parts[1])
    assert vals["IDENT_BITMATCH"] == 1.0
    assert vals["INT8_MIX_ERR"] < 0.05        # one int8 roundtrip
    for spec in ("identity", "int8ef", "top_k0.5ef", "rand_k0.5ef"):
        assert vals[f"SENDS_MATCH_{spec}"] == 1.0
    for spec in ("int8ef", "top_k0.5ef", "rand_k0.5ef"):
        assert np.isfinite(vals[f"XERR_{spec}"])
        assert vals[f"XERR_{spec}"] < 0.05    # tracks the exact run


SCRIPT_MOE_SM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.models.moe import init_moe, moe
from repro.models.layers import Maker
from repro.distributed.sharding import make_rules, use_rules

cfg0 = dataclasses.replace(get_config("granite-moe-3b-a800m").reduced(),
                           capacity_factor=8.0)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
p = init_moe(Maker(jax.random.PRNGKey(0), jnp.float32), cfg0)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg0.d_model))

def loss(c):
    return lambda p, x: (moe(p, x, c)[0] ** 2).sum() + 0.1 * moe(p, x, c)[1]

g_ref = jax.grad(loss(cfg0))(p, x)
for impl in ("batched", "shard_map"):
    cfg = dataclasses.replace(cfg0, moe_route_groups=4,
                              moe_group_impl=impl)
    rules = make_rules(cfg, mesh, fsdp=True)
    with mesh, use_rules(rules):
        g = jax.jit(jax.grad(loss(cfg)))(p, x)
    rel = max(float(np.abs(np.asarray(g_ref[k]) - np.asarray(g[k])).max()
                    / (np.abs(np.asarray(g_ref[k])).max() + 1e-9))
              for k in g_ref)
    print("GRADERR_" + impl, rel)
"""


def test_moe_grouped_impls_grad_match(tmp_path):
    """Both grouped-MoE impls (batched / custom-vjp shard_map) match the
    global-routing gradient under a sharded mesh (§Perf-1/2)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT_MOE_SM.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = {}
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2:
            vals[parts[0]] = float(parts[1])
    assert vals["GRADERR_batched"] < 2e-3
    assert vals["GRADERR_shard_map"] < 2e-3


SCRIPT_XPOD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.mixing import mix_apply
from jax import shard_map
from repro.distributed.collectives import RingWeights, ring_mix

mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
w = RingWeights.metropolis_ring(8)
z = jax.random.normal(jax.random.PRNGKey(0), (8, 5))
def local(zz):
    return jax.tree.map(lambda a: a[None], ring_mix(
        jax.tree.map(lambda a: a[0], zz), ("pod", "data"), w))
mixed = jax.jit(shard_map(local, mesh=mesh,
                              in_specs=P(("pod", "data")),
                              out_specs=P(("pod", "data")),
                              check_vma=False))(z)
dense = mix_apply(w.to_network().W_jnp(), z)
print("XPOD_ERR", float(jnp.abs(mixed - dense).max()))
"""


def test_cross_pod_ring_matches_dense_mixing(tmp_path):
    """Multi-pod DAGM ring: ppermute over the flattened ('pod','data')
    axes equals dense-W ring mixing (the 32-agent cross-pod ring)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT_XPOD.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    err = float(out.stdout.split("XPOD_ERR")[1].split()[0])
    assert err < 1e-6


SCRIPT_SOLVE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import quadratic_bilevel
from repro.distributed.dagm_sharded import make_sharded_dagm
from repro.optim import inverse_sqrt_schedule
from repro.solve import ScheduleSpec, sharded_spec, solve
import dataclasses

n, K = 8, 12
mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
prob = quadratic_bilevel(n, 3, 4, seed=0)
curv = float(max(np.linalg.eigvalsh(np.asarray(prob.data["A"][i])).max()
                 for i in range(n)))
spec = sharded_spec(alpha=0.05, beta=0.1, M=10, U=5, curvature=curv, K=K)

# --- 1. solve(tier="sharded") == hand-driven legacy step loop, bitwise ---
res = solve(prob, None, spec, mesh=mesh, seed=0)
step, _ = make_sharded_dagm(lambda x, y, b: prob.g(x, y, b),
                            lambda x, y, b: prob.f(x, y, b), spec, mesh)
x = jnp.zeros((n, 3))
y = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, 4))
for _ in range(K):
    x, y, m = step(x, y, prob.data)
print("SOLVE_BITEXACT", int(np.array_equal(np.asarray(res.x), np.asarray(x))
                            and np.array_equal(np.asarray(res.y),
                                               np.asarray(y))))
print("METRIC_ROUNDS", res.metrics["outer_loss"].shape[0])

# --- 2. decaying-alpha schedule runs through ONE compiled step ---
dec = dataclasses.replace(
    spec, schedule=ScheduleSpec(alpha=inverse_sqrt_schedule(0.05),
                                beta=0.1))
res_dec = solve(prob, None, dec, mesh=mesh, seed=0)
print("DEC_FINITE", int(np.isfinite(np.asarray(res_dec.x)).all()))
print("DEC_DIFFERS", int(not np.array_equal(np.asarray(res_dec.x),
                                            np.asarray(res.x))))
print("LEDGER_SENDS", float(res.metrics["comm_sends"][-1]))
"""


def test_solve_sharded_tier(tmp_path):
    """`repro.solve.solve(tier="sharded")`: constant schedules are
    bit-exact with the hand-driven legacy step loop, per-round metric
    trajectories come back stacked, and a decaying-alpha schedule runs
    through the same compiled step (coefficients are operands)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT_SOLVE.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = {}
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2:
            vals[parts[0]] = float(parts[1])
    assert vals["SOLVE_BITEXACT"] == 1
    assert vals["METRIC_ROUNDS"] == 12
    assert vals["DEC_FINITE"] == 1
    assert vals["DEC_DIFFERS"] == 1
    assert vals["LEDGER_SENDS"] == 16.0    # (M + U + 1) per round


SCRIPT_SOLVE_COMM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.core import quadratic_bilevel
from repro.distributed.dagm_sharded import (ShardedRoundCoeffs,
                                            make_sharded_dagm,
                                            open_sharded_channels,
                                            sharded_round_coeffs)
from repro.optim import inverse_sqrt_schedule
from repro.solve import sharded_spec, solve

n, K, seed = 8, 12, 3
mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
prob = quadratic_bilevel(n, 3, 4, seed=0)
curv = float(max(np.linalg.eigvalsh(np.asarray(prob.data["A"][i])).max()
                 for i in range(n)))


def same_result(a, b):
    return (np.array_equal(np.asarray(a.x), np.asarray(b.x))
            and np.array_equal(np.asarray(a.y), np.asarray(b.y))
            and set(a.metrics) == set(b.metrics)
            and all(np.array_equal(a.metrics[k], b.metrics[k])
                    for k in a.metrics))


for name, persist in (("persist", True), ("stoch", False)):
    spec = sharded_spec(alpha=inverse_sqrt_schedule(0.05), beta=0.1, M=4,
                        U=3, curvature=curv, comm="int8+ef",
                        persist_ef=persist, K=K)
    # the first call of a key traces its program; a repeat runs the
    # program solve() kept, bitwise the same
    t0 = obs.counter_value("jit_traces_total", name="sharded_dagm_run")
    res = solve(prob, None, spec, mesh=mesh, seed=seed)
    t1 = obs.counter_value("jit_traces_total", name="sharded_dagm_run")
    again = solve(prob, None, spec, mesh=mesh, seed=seed)
    t2 = obs.counter_value("jit_traces_total", name="sharded_dagm_run")
    print("RUN_TRACES_" + name, int(t1 - t0 == 1 and t2 - t1 == 0
                                    and same_result(res, again)))
    # the per-round step driven by hand, with the same keys, channels
    # and coefficients
    step, w = make_sharded_dagm(lambda x, y, b: prob.g(x, y, b),
                                lambda x, y, b: prob.f(x, y, b), spec,
                                mesh, schedule_hp=True)
    sched = spec.schedule.materialize(K)
    x = jnp.zeros((n, 3), jnp.float32)
    y = 0.01 * jax.random.normal(jax.random.PRNGKey(seed), (n, 4),
                                 jnp.float32)
    cs = open_sharded_channels(spec, x, y, seed) if persist else None
    rows = []
    for k in range(K):
        hp = ShardedRoundCoeffs(*(jnp.float32(c) for c in
                                  sharded_round_coeffs(
                                      float(sched.alpha[k]),
                                      float(sched.beta[k]), curv,
                                      w.w_self)))
        if persist:
            x, y, m, cs = step(x, y, prob.data, cs, hp)
        else:
            key = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5eed), k)
            x, y, m = step(x, y, prob.data, key, hp)
        rows.append(jax.tree.map(np.asarray, m))
    same = (np.array_equal(np.asarray(res.x), np.asarray(x))
            and np.array_equal(np.asarray(res.y), np.asarray(y))
            and all(np.array_equal(res.metrics[key],
                                   np.stack([r[key] for r in rows]))
                    for key in rows[0]))
    if persist:
        same = same and all(
            np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
            zip(jax.tree.leaves(res.channels), jax.tree.leaves(cs)))
    print("SOLVE_BITEXACT_" + name, int(same))
    rres = solve(prob, None, spec, mesh=mesh, seed=seed,
                 recorder=obs.RecorderSpec(capacity=16))
    print("RECORDED_BITSAME_" + name, int(
        np.array_equal(np.asarray(rres.x), np.asarray(res.x))
        and np.array_equal(np.asarray(rres.y), np.asarray(res.y))
        and rres.extras["flight"].shape[0] == K))

# --- solve()'s program cache on the sharded tier ---
import dataclasses
from repro.solve.api import _SHARDED_PROGRAMS

traces = [0]


def on_compile_event(event, secs, **_):
    if event == "/jax/core/compile/jaxpr_trace_duration":
        traces[0] += 1


jax.monitoring.register_event_duration_secs_listener(on_compile_event)


def runs():
    return obs.counter_value("jit_traces_total", name="sharded_dagm_run")


def cache(outcome):
    return obs.counter_value("solve_program_cache_total", tier="sharded",
                             outcome=outcome)


def cold(spec, seed):
    # the call with solve()'s program cache emptied first
    _SHARDED_PROGRAMS.clear()
    t = runs()
    res = solve(prob, None, spec, mesh=mesh, seed=seed)
    assert runs() == t + 1
    return res


def ledger_rows(ledger):
    return dict((k, (ch.sends, ch.bytes, ch.floats))
                for k, ch in ledger.channels.items())


spec = sharded_spec(alpha=0.05, beta=0.1, M=4, U=3, curvature=curv, K=K)
m0 = cache("miss")
solve(prob, None, spec, mesh=mesh, seed=1)
traces[0], t0, h0 = 0, runs(), cache("hit")
hit = solve(prob, None, spec, mesh=mesh, seed=2)
print("CACHE_REPEAT_NO_TRACE", int(traces[0] == 0 and runs() == t0
                                   and cache("hit") == h0 + 1
                                   and cache("miss") == m0 + 1))
ref = cold(spec, 2)
print("CACHE_REPEAT_BITWISE", int(
    traces[0] > 0 and same_result(hit, ref)
    and ledger_rows(hit.ledger) == ledger_rows(ref.ledger)
    and hit.ledger.total_bytes == ref.ledger.total_bytes > 0))
for name, other in (
        ("curvature", dataclasses.replace(spec, curvature=1.5 * curv)),
        ("K", dataclasses.replace(spec, K=K - 4))):
    solve(prob, None, spec, mesh=mesh, seed=1)
    m0, t0 = cache("miss"), runs()
    res = solve(prob, None, other, mesh=mesh, seed=1)
    print("CACHE_OWN_PROGRAM_" + name, int(
        cache("miss") == m0 + 1 and runs() == t0 + 1
        and same_result(res, cold(other, 1))))
swept = sharded_spec(alpha=inverse_sqrt_schedule(0.05), beta=0.1, M=4, U=3,
                     curvature=curv, K=K)
first = solve(prob, None, spec, mesh=mesh, seed=1)
t0, h0 = runs(), cache("hit")
second = solve(prob, None, swept, mesh=mesh, seed=1)
print("CACHE_SCHEDULE_SHARED", int(
    runs() == t0 and cache("hit") == h0 + 1
    and not np.array_equal(np.asarray(first.x), np.asarray(second.x))
    and same_result(second, cold(swept, 1))
    and same_result(first, cold(spec, 1))))
"""


@pytest.fixture(scope="module")
def solve_comm_vals():
    """The values SCRIPT_SOLVE_COMM prints, from one run of it."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT_SOLVE_COMM.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = {}
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2:
            vals[parts[0]] = float(parts[1])
    return vals


def test_solve_sharded_compressed_scan_matches_step_loop(solve_comm_vals):
    """`solve(tier="sharded")` scans the per-round step on the device:
    a persist_ef int8+ef solve and a stochastic int8+ef solve (decaying
    alpha) equal the hand-driven per-round step loop with the same
    channels and keys (`fold_in(PRNGKey(seed ^ 0x5eed), k)`) bitwise,
    with and without the flight recorder.  The first solve() of a key
    traces its run program (`sharded_dagm_run`) once, and a repeat
    traces nothing and returns the same result."""
    vals = solve_comm_vals
    for name in ("persist", "stoch"):
        assert vals[f"RUN_TRACES_{name}"] == 1
        assert vals[f"SOLVE_BITEXACT_{name}"] == 1
        assert vals[f"RECORDED_BITSAME_{name}"] == 1


def test_repeat_sharded_solve_traces_nothing_bitwise(solve_comm_vals):
    """A repeat call of a key traces nothing (no jaxpr trace at all,
    `sharded_dagm_run` unchanged, one hit and one miss counted) and
    returns x, y, metrics and a ledger equal to a cold call's."""
    assert solve_comm_vals["CACHE_REPEAT_NO_TRACE"] == 1
    assert solve_comm_vals["CACHE_REPEAT_BITWISE"] == 1


@pytest.mark.parametrize("change", ["curvature", "K"])
def test_sharded_trace_constants_get_their_own_programs(solve_comm_vals,
                                                        change):
    """The curvature (a constant of the DIHGP coefficients' program) and
    K (the scan length) key the program: each is a miss that traces,
    and equals a cold call."""
    assert solve_comm_vals["CACHE_OWN_PROGRAM_" + change] == 1


def test_sharded_schedule_sweep_shares_one_program(solve_comm_vals):
    """Two specs that differ only in the α schedule share one trace, and
    each is bitwise equal to a cold solve() with its own schedule."""
    assert solve_comm_vals["CACHE_SCHEDULE_SHARED"] == 1


SCRIPT_FLIGHT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.core import quadratic_bilevel
from repro.distributed.dagm_sharded import sharded_comm_ledger
from repro.solve import dagm_spec, sharded_spec, solve
from repro.topology import make_network

n, d1, d2, K, curv = 8, 3, 4, 12, 6.0
prob = quadratic_bilevel(n, d1, d2, seed=0)
mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
spec = sharded_spec(alpha=0.05, beta=0.1, M=10, U=5, curvature=curv, K=K)

# --- 1. recorder= is bitwise-inert and adds zero retraces ---
base = solve(prob, None, spec, mesh=mesh, seed=0)
t0 = obs.counter_value("jit_traces_total", name="sharded_dagm_run")
res = solve(prob, None, spec, mesh=mesh, seed=0,
            recorder=obs.RecorderSpec(capacity=32))
t1 = obs.counter_value("jit_traces_total", name="sharded_dagm_run")
print("TRACES_DELTA", t1 - t0)
print("BITSAME", int(np.array_equal(np.asarray(base.x), np.asarray(res.x))
                     and np.array_equal(np.asarray(base.y),
                                        np.asarray(res.y))))
print("METRIC_KEYS_SAME", int(set(res.metrics) == set(base.metrics)))

# --- 2. flight rows: shape, round index, wire == static ledger ---
fl = res.extras["flight"]
print("ROWS", fl.shape[0])
print("COLS", fl.shape[1])
print("ROUND_OK", int(fl[:, 0].tolist() == [float(k) for k in range(K)]))
iw = obs.FIELDS.index("wire_bytes")
ia = obs.FIELDS.index("alive_fraction")
local = jax.tree.map(lambda a: a[0], (res.x, res.y))
led = [sharded_comm_ledger(spec, local[0], local[1],
                           rounds=k + 1).total_bytes for k in range(K)]
print("WIRE_EXACT", int(all(float(fl[k, iw]) == float(led[k])
                            for k in range(K))))
print("ALIVE_OK", int(bool(np.all(fl[:, ia] == 1.0))))

# --- 3. gap/penalty columns agree with the reference-tier recorder
#        on the same problem, ring, and init ---
net = make_network("ring", n)
y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, d2), jnp.float32)
rspec = dagm_spec(alpha=0.05, beta=0.1, K=K, M=10, U=5,
                  dihgp="matrix_free", curvature=curv)
rres = solve(prob, net, rspec, x0=jnp.zeros((n, d1), jnp.float32), y0=y0,
             seed=0, recorder=obs.RecorderSpec(capacity=32))
rfl = rres.extras["flight"]
ig = obs.FIELDS.index("outer_gap_sq")
ip = obs.FIELDS.index("penalty")
gerr = np.max(np.abs(fl[:, ig] - rfl[:, ig])) / \
    max(np.max(np.abs(rfl[:, ig])), 1e-12)
perr = np.max(np.abs(fl[:, ip] - rfl[:, ip])) / \
    max(np.max(np.abs(rfl[:, ip])), 1e-12)
print("GAP_RELERR", gerr)
print("PEN_RELERR", perr)
print("X_MAXDIFF", float(np.max(np.abs(np.asarray(res.x)
                                       - np.asarray(rres.x)))))
"""


def test_sharded_flight_recorder(tmp_path):
    """`solve(tier="sharded", recorder=...)`: recorder-off runs stay
    bit-identical with zero added retraces, flight rows carry ordered
    round indices with the wire column exactly equal to the static
    `sharded_comm_ledger`, and the gap/penalty columns agree with the
    reference-tier recorder on the same ring and init."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = SCRIPT_FLIGHT.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = {}
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2:
            vals[parts[0]] = float(parts[1])
    assert vals["TRACES_DELTA"] == 1.0   # one trace of the recorded run
    assert vals["BITSAME"] == 1
    assert vals["METRIC_KEYS_SAME"] == 1
    assert vals["ROWS"] == 12 and vals["COLS"] == 5
    assert vals["ROUND_OK"] == 1
    assert vals["WIRE_EXACT"] == 1
    assert vals["ALIVE_OK"] == 1
    # f32 accumulation across shard_map pmean vs the dense reference
    assert vals["GAP_RELERR"] < 1e-4
    assert vals["PEN_RELERR"] < 1e-4
    assert vals["X_MAXDIFF"] < 1e-5     # same trajectory, two runtimes
