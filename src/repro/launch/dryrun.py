import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST run before any jax import: jax locks the device count at first init.
# Only the dry-run sees 512 placeholder devices; tests/benches see 1.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combination
against the production mesh, and emit memory / cost / roofline artifacts.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama2-7b --shape train_4k \
        --step fdlora_round --multi-pod     # the paper-technique lowering

Outputs JSON to experiments/dryrun/<arch>__<shape>__<mesh>__<step>[__<variant>].json
"""
import argparse
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import (ALL_ARCHS, config_for_shape, get_shape,
                                    shape_supported)
from repro.core.lora import adapter_specs, init_adapters, lora_scale
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models.api import get_model
from repro.training.optimizers import adamw, sgd
from repro.training.train_step import make_lora_train_step

# the chip the production mesh is made of; roofline terms use its peaks
DEVICE_KIND = "TPU v5 lite"


def _shardings(mesh, spec_tree, shape_tree):
    """NamedShardings; axes that don't divide a dim are dropped (replicated)."""
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(spec, sds):
        entries = list(spec) + [None] * (len(sds.shape) - len(tuple(spec)))
        out = []
        for dim, e in zip(sds.shape, entries):
            names = e if isinstance(e, tuple) else ((e,) if e else ())
            kept, prod = [], 1
            for n in names:
                if n in axis_size and dim % (prod * axis_size[n]) == 0:
                    kept.append(n)
                    prod *= axis_size[n]
            out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
        return NamedSharding(mesh, P(*out))

    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda s: isinstance(s, P))


def _opt_state_specs(adapter_spec):
    return {"mu": adapter_spec, "nu": adapter_spec, "count": P()}


def build_train(model, cfg, mesh, shape_name):
    """Paper-faithful train step: LoRA-only SFT (frozen base)."""
    opt = adamw(lr=2e-4)
    step = make_lora_train_step(model, cfg, opt)
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ad_s = jax.eval_shape(partial(init_adapters, cfg=cfg), jax.random.PRNGKey(0))
    opt_s = jax.eval_shape(opt.init, ad_s)
    batch_s = sp.train_inputs(cfg, shape_name)

    pspec = model.param_specs()
    adspec = adapter_specs(cfg)
    in_shardings = (
        _shardings(mesh, pspec, params_s),
        _shardings(mesh, adspec, ad_s),
        _shardings(mesh, _opt_state_specs(adspec), opt_s),
        _shardings(mesh, sp.train_input_specs(cfg, mesh, shape_name), batch_s),
    )
    out_shardings = (in_shardings[1], in_shardings[2], None)
    jitted = jax.jit(step, in_shardings=in_shardings, out_shardings=out_shardings)
    args = (params_s, ad_s, opt_s, batch_s)
    tokens = batch_s["tokens"].shape[0] * batch_s["tokens"].shape[1]
    return jitted, args, rl.model_flops_train(cfg, tokens)


def build_prefill(model, cfg, mesh, shape_name):
    """Inference prefill: full forward, unembed last position only."""
    scale = lora_scale(cfg)

    def step(params, adapters, batch):
        return model.forward(params, batch, adapters=adapters,
                             lora_scale=scale, last_only=not cfg.is_encdec)[0]

    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ad_s = jax.eval_shape(partial(init_adapters, cfg=cfg), jax.random.PRNGKey(0))
    batch_s = sp.train_inputs(cfg, shape_name)
    batch_s.pop("loss_mask")
    in_shardings = (
        _shardings(mesh, model.param_specs(), params_s),
        _shardings(mesh, adapter_specs(cfg), ad_s),
        _shardings(mesh, {k: v for k, v in
                          sp.train_input_specs(cfg, mesh, shape_name).items()
                          if k != "loss_mask"}, batch_s),
    )
    jitted = jax.jit(step, in_shardings=in_shardings)
    tokens = batch_s["tokens"].shape[0] * batch_s["tokens"].shape[1]
    return jitted, (params_s, ad_s, batch_s), rl.model_flops_decode(cfg, tokens)


def _serve2d(spec_tree, shape_tree, mesh):
    """§Perf serving iteration: 1-D ("model"-only) weight sharding leaves
    the data axis idle at decode, so big models replicate 16× and blow HBM
    (kimi decode: 187 GiB/dev). Shard the first large unsharded dim of every
    weight over "data" as well (2-D weight sharding, standard for
    inference)."""
    data = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)

    def fix(spec, sds):
        entries = list(spec) + [None] * (len(sds.shape) - len(tuple(spec)))
        used = {n for e in entries for n in
                (e if isinstance(e, tuple) else (e,)) if n}
        if "data" in used or len(sds.shape) < 2:
            return P(*entries)
        for i, (dim, e) in enumerate(zip(sds.shape, entries)):
            if e is None and dim >= 256 and dim % data == 0:
                entries[i] = "data"
                break
        return P(*entries)

    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda s: isinstance(s, P))


def build_decode(model, cfg, mesh, shape_name):
    """Serve step: ONE new token against a seq_len cache/state."""
    sh = get_shape(shape_name)
    scale = lora_scale(cfg)

    def step(params, adapters, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, adapters=adapters,
                                 lora_scale=scale)

    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ad_s = jax.eval_shape(partial(init_adapters, cfg=cfg), jax.random.PRNGKey(0))
    cache_s = jax.eval_shape(
        partial(model.init_decode_cache, sh.global_batch, sh.seq_len))
    dec_in = sp.decode_inputs(cfg, shape_name)
    cache_spec = model.decode_cache_specs()
    pspec = model.param_specs()
    if globals().get("_SERVE2D"):
        pspec = _serve2d(pspec, params_s, mesh)
    in_shardings = (
        _shardings(mesh, pspec, params_s),
        _shardings(mesh, adapter_specs(cfg), ad_s),
        _shardings(mesh, cache_spec, cache_s),
        _shardings(mesh, sp.decode_input_specs(cfg, mesh, shape_name), dec_in),
    )
    jitted = jax.jit(step, in_shardings=(in_shardings[0], in_shardings[1],
                                         in_shardings[2],
                                         in_shardings[3]["tokens"],
                                         in_shardings[3]["pos"]))
    args = (params_s, ad_s, cache_s, dec_in["tokens"], dec_in["pos"])
    return jitted, args, rl.model_flops_decode(cfg, sh.global_batch)


def build_fdlora_round(model, cfg, mesh, shape_name, n_clients=2, K=3):
    """The paper's technique as one lowered program: K inner steps per client
    (clients on the pod axis) + the single cross-pod outer aggregation."""
    from repro.core.outer_opt import make_outer_optimizer
    from repro.federated.distributed import (client_stacked_specs,
                                             make_fdlora_round_step)
    sh = get_shape(shape_name)
    inner = adamw(lr=2e-4)
    outer = make_outer_optimizer("nesterov", 1e-3, 0.5)
    round_step = make_fdlora_round_step(
        model, cfg, inner, outer, K,
        compress_outer=globals().get("_FDLORA_COMPRESS", "none"))

    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    theta_s = jax.eval_shape(partial(init_adapters, cfg=cfg), jax.random.PRNGKey(0))
    inner_st = jax.eval_shape(inner.init, theta_s)
    outer_st = jax.eval_shape(outer.init, theta_s)

    def stack(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_clients,) + s.shape, s.dtype), tree)

    state_s = {"inner_opt": stack(inner_st), "outer_opt": outer_st}
    B_local = sh.global_batch // n_clients
    batches_s = {
        "tokens": jax.ShapeDtypeStruct((n_clients, K, B_local, sh.seq_len), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((n_clients, K, B_local, sh.seq_len), jnp.int32),
    }

    adspec = adapter_specs(cfg)
    stacked_adspec = client_stacked_specs(adspec)
    state_spec = {"inner_opt": {"mu": stacked_adspec, "nu": stacked_adspec,
                                "count": P("pod")},
                  "outer_opt": {"v": adspec}}
    batch_spec = {"tokens": P("pod", None, "data", None),
                  "loss_mask": P("pod", None, "data", None)}

    in_shardings = (
        _shardings(mesh, model.param_specs(), params_s),
        _shardings(mesh, adspec, theta_s),
        _shardings(mesh, state_spec, state_s),
        _shardings(mesh, batch_spec, batches_s),
    )
    jitted = jax.jit(round_step, in_shardings=in_shardings)
    args = (params_s, theta_s, state_s, batches_s)
    tokens = n_clients * K * B_local * sh.seq_len
    return jitted, args, rl.model_flops_train(cfg, tokens)


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode, "fdlora_round": build_fdlora_round}


def _reduced_cfg(cfg, n_periods: int):
    """Depth-reduced, fully-unrolled variant for exact cost extraction."""
    period = len(cfg.layer_pattern)
    kw = dict(n_layers=n_periods * period, scan_unroll=n_periods)
    if cfg.is_encdec:
        kw["n_encoder_layers"] = n_periods
        kw["n_layers"] = n_periods
    return cfg.with_overrides(**kw)


def _compile_once(cfg, mesh, shape_name, step):
    model = get_model(cfg)
    jitted, args, model_flops = BUILDERS[step](model, cfg, mesh, shape_name)
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    return compiled, model_flops


def _extrapolated_cost(cfg, mesh, shape_name, step):
    """Exact flops/bytes/collectives via depth extrapolation.

    XLA's cost_analysis counts a while (scan) body ONCE, so the rolled
    production graph under-reports by ~n_layers. Fully unrolling the real
    depth is exact but compiles for minutes. Instead we compile 1-period and
    2-period *unrolled* variants (seconds each; every period is identical)
    and extrapolate: cost(P) = c1 + (P-1)·(c2 - c1). Embedding/unembedding
    and other depth-independent terms live in c1 and are counted once."""
    P = cfg.n_layers if cfg.is_encdec else cfg.n_periods
    if P == 1:
        c, _ = _compile_once(cfg.with_overrides(scan_unroll=1), mesh,
                             shape_name, step)
        cost = c.cost_analysis()
        colls = rl.parse_collectives(c.as_text())
        return (float(cost.get("flops", 0)), float(cost.get("bytes accessed", 0)),
                sum(x.per_chip_bytes for x in colls), colls)
    # Two depths: auto-sharding makes the depth-independent part mildly
    # depth-dependent; a lever arm of >= 2 periods keeps that noise small
    # relative to the per-period cost (which dominates for train shapes).
    pa, pb = (1, 3) if P >= 3 else (1, P)
    out = []
    for p in (pa, pb):
        c, _ = _compile_once(_reduced_cfg(cfg, p), mesh, shape_name, step)
        cost = c.cost_analysis()
        colls = rl.parse_collectives(c.as_text())
        out.append((float(cost.get("flops", 0)),
                    float(cost.get("bytes accessed", 0)),
                    sum(x.per_chip_bytes for x in colls), colls))
    (fa, ba, cba, colls_a), (fb, bb, cbb, _) = out

    def total(ca, cb):
        per = max((cb - ca) / (pb - pa), 0.0)
        return max(ca - pa * per, 0.0) + P * per

    return total(fa, fb), total(ba, bb), total(cba, cbb), colls_a


# §Perf hillclimb variants: config overrides applied on top of the baseline.
VARIANTS = {
    "baseline": {},
    "gqa_grouped": {"attn_impl": "grouped"},
    "sm_bf16": {"attn_softmax_dtype": "bfloat16"},
    "opt_attn": {"attn_impl": "grouped", "attn_softmax_dtype": "bfloat16"},
    "no_remat": {"remat": False},
    "remat_dots": {"remat_policy": "dots"},
    "moe_cap1": {"moe_capacity_factor": 1.0},
    "opt_moe": {"moe_capacity_factor": 1.0, "remat_policy": "dots"},
    # fdlora_round-only variant (handled in build_fdlora_round):
    "bf16_outer": {},
    "serve2d": {},
}


def run_one(arch: str, shape_name: str, multi_pod: bool, step: str = "auto",
            variant: str = "baseline", out_dir: str = "experiments/dryrun",
            dump_hlo: bool = False, smoke: bool = False,
            with_cost: bool = True):
    if not shape_supported(arch, shape_name):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "DESIGN.md §5: whisper decoder context is bounded"}
    cfg = config_for_shape(arch, shape_name, smoke=smoke)
    cfg = cfg.with_overrides(**VARIANTS.get(variant, {}))
    global _FDLORA_COMPRESS, _SERVE2D
    _FDLORA_COMPRESS = "bf16" if variant == "bf16_outer" else "none"
    _SERVE2D = variant == "serve2d"
    if step == "auto":
        step = INPUT_SHAPES[shape_name].kind
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        # 1) Full-depth rolled compile: proves the combination lowers on this
        #    mesh and yields the realistic per-device memory analysis.
        jitted_args = BUILDERS[step](get_model(cfg), cfg, mesh, shape_name)
        lowered = jitted_args[0].lower(*jitted_args[1])
        model_flops = jitted_args[2]
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        # 2) Depth-extrapolated exact cost terms (single-pod roofline only;
        #    the multi-pod pass proves lowering + memory, per the task spec).
        if with_cost:
            flops, hbm, coll_bytes, colls = _extrapolated_cost(
                cfg, mesh, shape_name, step)
        else:
            flops = hbm = coll_bytes = 0.0
            colls = rl.parse_collectives(hlo)

    chips = mesh.devices.size
    roof = rl.analyze({"flops": flops, "bytes accessed": hbm}, "", chips,
                      model_flops, device_kind=DEVICE_KIND)
    roof.collective_bytes = coll_bytes
    roof.collective_s = coll_bytes / rl.peaks(DEVICE_KIND).ici_bw
    roof.n_collectives = len(colls)
    roof.coll_by_op = {}
    for c in colls:
        roof.coll_by_op[c.op] = roof.coll_by_op.get(c.op, 0.0) + c.per_chip_bytes
    roof.dominant = max((("compute", roof.compute_s), ("memory", roof.memory_s),
                         ("collective", roof.collective_s)),
                        key=lambda kv: kv[1])[0]
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step": step, "variant": variant, "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "params": cfg.count_params(),
        "active_params": cfg.count_active_params(),
        "lora_params": cfg.count_lora_params(),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "roofline": roof.to_dict(),
    }
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{result['mesh']}__{step}"
    if variant != "baseline":
        tag += f"__{variant}"
    if smoke:
        tag += "__smoke"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    if dump_hlo:
        with open(os.path.join(out_dir, tag + ".hlo.txt"), "w") as f:
            f.write(hlo)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ALL_ARCHS + ["all"])
    ap.add_argument("--shape", required=True,
                    choices=list(INPUT_SHAPES) + ["all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--step", default="auto",
                    choices=["auto", "train", "prefill", "decode", "fdlora_round"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--no-cost", action="store_true",
                    help="lower+compile+memory only (multi-pod sweeps)")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip combos whose JSON artifact already exists")
    args = ap.parse_args(argv)

    archs = ALL_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    failures = []
    for arch in archs:
        for shape in shapes:
            mesh_tag = "2x16x16" if args.multi_pod else "16x16"
            step_tag = args.step if args.step != "auto" else INPUT_SHAPES[shape].kind
            tag = f"{arch}__{shape}__{mesh_tag}__{step_tag}"
            if args.variant != "baseline":
                tag += f"__{args.variant}"
            if args.skip_existing and os.path.exists(
                    os.path.join(args.out_dir, tag + ".json")):
                print(f"SKIP-EXISTING {arch} {shape}")
                continue
            try:
                r = run_one(arch, shape, args.multi_pod, args.step,
                            args.variant, args.out_dir, args.dump_hlo,
                            args.smoke, with_cost=not args.no_cost)
            except Exception as e:  # keep sweeping; report at the end
                failures.append((arch, shape, repr(e)[:300]))
                print(f"FAIL {arch} {shape}: {repr(e)[:300]}")
                sys.stdout.flush()
                continue
            if r.get("skipped"):
                print(f"SKIP {arch} {shape}: {r['reason']}")
                continue
            roof = r["roofline"]
            print(f"OK {arch} {shape} {r['mesh']} {r['step']} "
                  f"compile={r['compile_s']}s "
                  f"compute={roof['compute_s']:.4f}s "
                  f"memory={roof['memory_s']:.4f}s "
                  f"coll={roof['collective_s']:.4f}s "
                  f"dom={roof['dominant']} useful={roof['useful_ratio']:.2f}")
            sys.stdout.flush()
    if failures:
        print(f"{len(failures)} FAILURES:")
        for a, s, e in failures:
            print(" ", a, s, e)
        sys.exit(1)


if __name__ == "__main__":
    main()
