"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes, with NO device allocation (ShapeDtypeStruct
stand-ins), and extract the roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json

The os.environ line below MUST run before ANY jax import (including
transitively via repro.*): jax locks the device count on first init.
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import re
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.configs.base import ArchConfig, InputShape
from repro.distributed.sharding import (make_rules, tree_param_sharding,
                                        use_rules)
from repro.launch.costs import (affine_correct, depth_pair,
                                flops_estimate, model_flops_convention,
                                reduced_depth)
from repro.launch.mesh import make_production_mesh, roofline_terms
from repro.models import build_model
from repro.models.steps import make_decode_step, make_prefill_step, \
    make_train_step
from repro.optim import adamw
from jax.sharding import NamedSharding, PartitionSpec as P

# long_500k policy (DESIGN.md §5): whisper skipped; SSM/hybrid native;
# attention archs use a sliding-window cache of this size:
LONG_WINDOW = 8192
SKIP = {("whisper-large-v3", "long_500k"):
        "encoder-decoder: 500k self-cache is semantically undefined "
        "(30s audio source); see DESIGN.md §5"}

COMPUTE_DTYPE = jnp.bfloat16


def microbatches_for(cfg: ArchConfig, shape: InputShape, mesh) -> int:
    """Grad-accumulation factor so remat'd activations fit HBM:
    saved bytes ≈ L × B_shard/mb × S × d × 2; target ≤ 2 GB."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = axes.get("data", 1) * axes.get("pod", 1)
    b_shard = max(shape.global_batch // dp, 1)
    layers = cfg.num_layers + cfg.encoder_layers
    bytes_act = layers * b_shard * shape.seq_len * cfg.d_model * 2
    mb = 1
    while bytes_act / mb > 2e9 and mb < b_shard:
        mb *= 2
    return mb


def batch_specs(cfg: ArchConfig, shape: InputShape, *, with_labels: bool):
    B, S = shape.global_batch, shape.seq_len
    spec = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    sh = {"tokens": ("batch", None)}
    if with_labels:
        spec["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        sh["labels"] = ("batch", None)
    if cfg.encoder_decoder:
        spec["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_frames, cfg.d_model), COMPUTE_DTYPE)
        sh["frames"] = ("batch", None, None)
    return spec, sh


def cache_logical_axes(cfg: ArchConfig, cache_shapes):
    """Logical axes for every cache leaf, matched by key path."""
    def leaf_axes(path, leaf):
        keys = [getattr(p, "key", str(p)) for p in path]
        name = keys[-1]
        nd = len(leaf.shape)
        if "xkv" in keys:                  # (L, B, frames, Hkv, hd)
            return (None, "batch", None, "kv_heads", None)
        if name in ("k", "v"):             # (L|n_inv, B, C, Hkv, hd)
            return (None, "batch", "cache_seq", "kv_heads", None)
        if name == "S" and cfg.attn_free:  # (L, B, H, hd, hd)
            return (None, "batch", "rwkv_heads", None, None)
        if name == "S":                    # mamba (L, B, H, hd, N)
            return (None, "batch", "ffn", None, None)
        if name == "conv":                 # (L, B, K-1, d_inner)
            return (None, "batch", None, "ffn")
        if name in ("tm_x", "cm_x"):       # (L, B, d)
            return (None, "batch", None)
        if name == "pos":
            return ()
        return tuple([None] * nd)
    return jax.tree_util.tree_map_with_path(leaf_axes, cache_shapes)


def input_specs(arch: str, shape_name: str):
    """Public API: ShapeDtypeStruct stand-ins for every model input of
    the given (arch × shape) combination."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        return batch_specs(cfg, shape, with_labels=True)[0]
    if shape.kind == "prefill":
        return batch_specs(cfg, shape, with_labels=False)[0]
    # decode: one new token + cache of seq_len
    model = build_model(cfg)
    window = LONG_WINDOW if (shape_name == "long_500k"
                             and not cfg.sliding_window
                             and not cfg.attn_free
                             and not cfg.shared_attn_every) else 0
    cache = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                 COMPUTE_DTYPE, window_override=window))
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return {"tokens": tokens, "cache": cache}


@dataclasses.dataclass
class DryRunResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    error: str = ""
    skip_reason: str = ""
    compile_s: float = 0.0
    flops: float = 0.0
    hbm_bytes_accessed: float = 0.0
    peak_memory_per_device: float = 0.0
    argument_size_per_device: float = 0.0
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    params_b: float = 0.0
    microbatches: int = 1
    # scan-corrected accounting (unrolled depth-pair extrapolation)
    flops_corrected: float = 0.0
    bytes_corrected: float = 0.0
    collective_bytes_corrected: float = 0.0
    analytic_flops_per_chip: float = 0.0
    model_flops_per_chip: float = 0.0
    useful_ratio: float = 0.0

    def roofline(self) -> dict:
        """Roofline terms in seconds.  compiled.cost_analysis() and the
        partitioned HLO are already PER-DEVICE quantities (the executable
        is the per-chip SPMD program), so no further division by chip
        count — verified against 2·N·B hand counts in tests.  Corrected
        values (scan-aware) are used when the accounting pass ran."""
        coll = self.collective_bytes_corrected or \
            sum(self.collective_bytes.values())
        flops = self.flops_corrected or self.flops
        byts = self.bytes_corrected or self.hbm_bytes_accessed
        return roofline_terms(flops, byts, coll)


_COLL_RE = re.compile(
    r"(\S+)\s*=\s*(?:\(.*?\)|\S+)\s*(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)")


def collective_bytes_from_hlo(hlo: str) -> dict[str, float]:
    """Sum result sizes of every collective op in the (per-device) HLO.

    Async `-start` ops carry tuple result types (operand alias +
    result); all tuple elements are counted, so async collectives are
    counted once at `-start` (the `-done` line is skipped)."""
    sizes: dict[str, float] = {}
    shape_re = re.compile(
        r"(bf16|f32|f16|s32|u32|s8|u8|f64|pred)\[([\d,]*)\]")
    bytes_of = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "f64": 8, "pred": 1}
    for line in hlo.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        m = re.search(r"(all-gather|all-reduce|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start|-done)?\(",
                      line)
        if not m or m.group(2) == "-done":
            continue
        op = m.group(1)
        region = line[line.index("=") + 1:m.start(1)]   # result type(s)
        total = 0
        for dt, dims in shape_re.findall(region):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * bytes_of[dt]
        sizes[op] = sizes.get(op, 0) + total
    return sizes


def build_step_and_args(cfg: ArchConfig, shape: InputShape, mesh, rules,
                        *, unroll: bool = False,
                        microbatches: int | None = None):
    """Returns (fn, arg_shapes, in_shardings)."""
    model = build_model(cfg)
    axes = model.param_axes()
    param_sh = tree_param_sharding(axes, rules)
    params_shape = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), COMPUTE_DTYPE))

    def named(*logical):
        return NamedSharding(mesh, rules.resolve(*logical))

    if shape.kind == "train":
        opt = adamw(1e-4)
        mb = microbatches if microbatches else \
            microbatches_for(cfg, shape, mesh)
        step = make_train_step(model, opt, microbatches=mb,
                               unroll=unroll)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        # opt state mirrors param shardings for mu/nu; step replicated
        opt_sh = type(opt_shape)(step=named(), mu=param_sh, nu=param_sh)
        bspec, bsh = batch_specs(cfg, shape, with_labels=True)
        batch_sh = {k: named(*v) for k, v in bsh.items()}
        return (step, (params_shape, opt_shape, bspec),
                (param_sh, opt_sh, batch_sh),
                {"microbatches": mb, "donate": (0, 1)})

    if shape.kind == "prefill":
        fn = make_prefill_step(model, cache_dtype=COMPUTE_DTYPE,
                               unroll=unroll)
        bspec, bsh = batch_specs(cfg, shape, with_labels=False)
        batch_sh = {k: named(*v) for k, v in bsh.items()}
        return fn, (params_shape, bspec), (param_sh, batch_sh), {}

    # decode
    fn = make_decode_step(model, unroll=unroll)
    window = LONG_WINDOW if (shape.name == "long_500k"
                             and not cfg.sliding_window
                             and not cfg.attn_free
                             and not cfg.shared_attn_every) else 0
    model_ic = build_model(cfg)
    cache_shape = jax.eval_shape(
        lambda: model_ic.init_cache(shape.global_batch, shape.seq_len,
                                    COMPUTE_DTYPE, window_override=window))
    cache_ax = cache_logical_axes(cfg, cache_shape)
    cache_sh = jax.tree.map(lambda a: named(*a), cache_ax,
                            is_leaf=lambda t: isinstance(t, tuple))
    tok_shape = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_sh = named("batch", None)
    return (fn, (params_shape, tok_shape, cache_shape),
            (param_sh, tok_sh, cache_sh), {"donate": (2,)})


def _compile_once(cfg, shape, mesh, rules, *, unroll=False,
                  microbatches=None):
    fn, args, shardings, extra = build_step_and_args(
        cfg, shape, mesh, rules, unroll=unroll, microbatches=microbatches)
    lowered = jax.jit(fn, in_shardings=shardings,
                      donate_argnums=extra.get("donate", ())).lower(*args)
    return lowered.compile(), extra


def accounting_pass(cfg, shape, mesh, rules, res: DryRunResult):
    """Two unrolled reduced-depth compiles → affine-in-L corrected
    flops / bytes / collective bytes (see launch/costs.py)."""
    l1, l2 = depth_pair(cfg)
    vals = {}
    for L in (l1, l2):
        c, _ = _compile_once(reduced_depth(cfg, L), shape, mesh, rules,
                             unroll=True, microbatches=1)
        cost = c.cost_analysis()
        vals[L] = (float(cost.get("flops", 0.0)),
                   float(cost.get("bytes accessed", 0.0)),
                   sum(collective_bytes_from_hlo(c.as_text()).values()))
    L = cfg.num_layers
    res.flops_corrected = affine_correct(vals[l1][0], vals[l2][0], l1, l2, L)
    res.bytes_corrected = affine_correct(vals[l1][1], vals[l2][1], l1, l2, L)
    res.collective_bytes_corrected = affine_correct(
        vals[l1][2], vals[l2][2], l1, l2, L)
    n_chips = int(np.prod(mesh.devices.shape))
    res.analytic_flops_per_chip = flops_estimate(cfg, shape) / n_chips
    model = build_model(cfg)
    n_active = int(model.param_count() *
                   (get_config(res.arch).active_param_count()
                    / max(get_config(res.arch).param_count(), 1)))
    res.model_flops_per_chip = model_flops_convention(
        cfg, shape, n_active) / n_chips
    if res.flops_corrected:
        res.useful_ratio = res.model_flops_per_chip / res.flops_corrected


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            verbose: bool = True, accounting: bool = False,
            moe_groups: int = 0, expert_parallel: bool = False,
            moe_impl: str = "batched", microbatches: int = 0
            ) -> DryRunResult:
    cfg = get_config(arch)
    if moe_groups and cfg.num_experts:
        cfg = dataclasses.replace(cfg, moe_route_groups=moe_groups,
                                  moe_group_impl=moe_impl)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    res = DryRunResult(arch=arch, shape=shape_name, mesh=mesh_name,
                       ok=False)
    if (arch, shape_name) in SKIP:
        res.skip_reason = SKIP[(arch, shape_name)]
        if verbose:
            print(f"[dryrun] SKIP {arch} × {shape_name}: {res.skip_reason}")
        return res

    # batch=1 decode cannot shard the batch axis; shard cache seq instead
    seq_cache = shape.kind == "decode"
    fsdp = shape.kind == "train"
    rules = make_rules(cfg, mesh, seq_shard_cache=seq_cache, fsdp=fsdp,
                       expert_parallel=expert_parallel)
    if shape.global_batch == 1:
        # batch=1 cannot shard over data: re-lay the cache sequence over
        # the freed axes instead (minus any axis kv_heads already owns).
        cs = "data" if rules.table.get("kv_heads") else ("data", "model")
        rules = dataclasses.replace(
            rules, table={**rules.table, "batch": None, "cache_seq": cs})

    t0 = time.time()
    try:
        with use_rules(rules):
            compiled, extra = _compile_once(
                cfg, shape, mesh, rules,
                microbatches=microbatches or None)
        res.compile_s = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        res.flops = float(cost.get("flops", 0.0))
        res.hbm_bytes_accessed = float(cost.get("bytes accessed", 0.0))
        res.peak_memory_per_device = float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0))
        res.argument_size_per_device = float(
            getattr(mem, "argument_size_in_bytes", 0))
        res.collective_bytes = collective_bytes_from_hlo(
            compiled.as_text())
        res.params_b = build_model(cfg).param_count() / 1e9
        res.microbatches = extra.get("microbatches", 1)
        if accounting:
            with use_rules(rules):
                accounting_pass(cfg, shape, mesh, rules, res)
        res.ok = True
        if verbose:
            rf = res.roofline()
            terms = {k: f'{v*1e3:.2f}ms' for k, v in rf.items()
                     if k != 'bottleneck'}
            print(f"[dryrun] OK {arch} × {shape_name} ({mesh_name}) "
                  f"compile={res.compile_s:.1f}s flops={res.flops:.3g} "
                  f"corr={res.flops_corrected:.3g} "
                  f"mem/dev={res.peak_memory_per_device/1e9:.2f}GB "
                  f"coll={sum(res.collective_bytes.values())/1e9:.3f}GB "
                  f"roofline={terms} bound={rf['bottleneck']}")
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        res.error = f"{type(e).__name__}: {e}"
        res.compile_s = time.time() - t0
        if verbose:
            print(f"[dryrun] FAIL {arch} × {shape_name}: {res.error[:500]}")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--accounting", action="store_true",
                    help="also run the unrolled cost-accounting compiles")
    ap.add_argument("--moe-groups", type=int, default=0,
                    help="group-local MoE routing domains (§Perf variant;"
                         " 0 = paper-faithful global routing)")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="experts over the model axis (§Perf variant)")
    ap.add_argument("--moe-impl", default="batched",
                    choices=["batched", "shard_map"])
    ap.add_argument("--microbatches", type=int, default=0,
                    help="override the grad-accumulation heuristic "
                         "(train shapes; §Perf-1 iter 6)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    kw = dict(multi_pod=args.multi_pod, accounting=args.accounting,
              moe_groups=args.moe_groups,
              expert_parallel=args.expert_parallel,
              moe_impl=args.moe_impl, microbatches=args.microbatches)
    results = []
    if args.all:
        for arch in ARCHS:
            for shape in INPUT_SHAPES:
                results.append(run_one(arch, shape, **kw))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        results.append(run_one(args.arch, args.shape, **kw))
    if args.out:
        with open(args.out, "w") as f:
            json.dump([dataclasses.asdict(r) for r in results], f,
                      indent=1)
    n_fail = sum(1 for r in results if not r.ok and not r.skip_reason)
    print(f"[dryrun] {sum(r.ok for r in results)} ok, {n_fail} failed, "
          f"{sum(1 for r in results if r.skip_reason)} skipped")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
