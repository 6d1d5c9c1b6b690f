"""Multi-pod dry run: prove every (arch × input-shape × mesh) combination
lowers, SPMD-partitions and compiles on the production mesh, and extract the
roofline inputs (FLOPs / HBM bytes / collective bytes / per-device memory).

The XLA_FLAGS line above MUST precede every other import — jax locks the
device count at first init. Do not set it globally: smoke tests and benches
run on 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --multi-pod
    ... --out results.jsonl        # append JSON records
"""
# The forced device count MUST be set before any other import — jax locks the
# device count at first init. (This is why these two lines lead the module.)
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.tree import path_str
from repro.launch import costs as C
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (
    batch_shardings,
    cache_shardings,
    opt_state_shardings,
    param_shardings,
    with_shardings,
)
from repro.launch.specs import (
    SHAPES,
    cache_specs,
    decode_window_override,
    input_specs,
    params_specs,
)
from repro.launch.steps import default_optimizer, make_prefill_step, make_serve_step, make_train_step
from repro.models import build_model

FSDP_PARAM_THRESHOLD = 3_000_000_000  # params; above this, weights also shard on data axes


def count_params(shapes) -> int:
    return int(sum(np.prod(l.shape, dtype=np.float64) for l in jax.tree.leaves(shapes)))


def active_param_fraction(cfg, params_shapes) -> float:
    """MoE active fraction for MODEL_FLOPS = 6·N_active·D."""
    if not cfg.num_experts:
        return 1.0
    leaves = jax.tree_util.tree_flatten_with_path(params_shapes)[0]
    total = moe = 0.0
    for path, leaf in leaves:
        n = float(np.prod(leaf.shape, dtype=np.float64))
        total += n
        p = path_str(path)
        if "/moe/" in p and ("/wi/" in p or "/wg/" in p or "/wo/" in p):
            moe += n
    active = total - moe * (1.0 - cfg.experts_per_token / cfg.num_experts)
    return active / total


def layer_trips(cfg) -> int:
    return max(1, cfg.n_layers // len(cfg.pattern))


def build_lowerable(cfg, shape, mesh, *, fsdp: bool, remat: bool = True, microbatches: int = 8):
    """Returns (fn, arg_structs tuple, donate_argnums, n_tokens)."""
    model = build_model(cfg)
    p_shapes = params_specs(cfg)
    p_shard = param_shardings(p_shapes, mesh, fsdp=fsdp)
    p_structs = with_shardings(p_shapes, p_shard)
    data = input_specs(cfg, shape)
    d_structs = with_shardings(data, batch_shardings(data, mesh))

    if shape.kind == "train":
        optimizer = default_optimizer(cfg, count_params(p_shapes))
        opt_shapes = jax.eval_shape(optimizer.init, p_shapes)
        o_structs = with_shardings(opt_shapes, opt_state_shardings(opt_shapes, mesh, fsdp=fsdp))
        fn = make_train_step(cfg, optimizer, remat=remat, microbatches=microbatches)
        n_tokens = data["tokens"].shape[0] * data["tokens"].shape[1]
        return fn, (p_structs, o_structs, d_structs), (0, 1), n_tokens

    if shape.kind == "prefill":
        fn = make_prefill_step(cfg)
        n_tokens = data["tokens"].shape[0] * data["tokens"].shape[1]
        return fn, (p_structs, d_structs), (), n_tokens

    # decode
    wo = decode_window_override(cfg, shape)
    fn = make_serve_step(cfg, window_override=wo)
    c_shapes = cache_specs(cfg, shape, p_shapes)
    c_structs = with_shardings(c_shapes, cache_shardings(c_shapes, mesh))
    n_tokens = shape.global_batch  # one new token per sequence
    return fn, (p_structs, d_structs["token"], c_structs, d_structs["pos"]), (2,), n_tokens


def _parse_override(kv: str):
    key, _, val = kv.partition("=")
    for cast in (int, float):
        try:
            return key, cast(val)
        except ValueError:
            continue
    if val in ("true", "false"):
        return key, val == "true"
    return key, val


def run_one(arch: str, shape_name: str, *, multi_pod: bool, fsdp: str = "auto",
            remat: bool = True, microbatches: int = 8, overrides: dict | None = None,
            tag: str = "", verbose: bool = True) -> dict:
    t0 = time.time()
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))

    p_shapes = params_specs(cfg)
    n_params = count_params(p_shapes)
    use_fsdp = (n_params > FSDP_PARAM_THRESHOLD) if fsdp == "auto" else (fsdp == "on")

    fn, arg_structs, donate, n_tokens = build_lowerable(
        cfg, shape, mesh, fsdp=use_fsdp, remat=remat, microbatches=microbatches
    )

    with mesh:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*arg_structs)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}

    # trip-aware global costs from the jaxpr
    jc = C.jaxpr_costs(fn, *arg_structs)
    trips = layer_trips(cfg)
    coll = C.collective_bytes(compiled.as_text(), loop_trip_count=trips)
    terms = C.roofline_terms(
        total_flops=jc.flops, total_bytes=jc.bytes, coll_bytes=coll["total"], chips=chips
    )
    act_frac = active_param_fraction(cfg, p_shapes)
    mf = (C.model_flops_train if shape.kind == "train" else C.model_flops_infer)(
        n_params, n_tokens, act_frac
    )

    record = {
        "arch": arch,
        "tag": tag,
        "overrides": overrides or {},
        "microbatches": microbatches,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "fsdp": use_fsdp,
        "n_params": n_params,
        "active_fraction": round(act_frac, 4),
        "n_tokens": n_tokens,
        "flops_global": jc.flops,
        "bytes_global": jc.bytes,
        "collective_bytes": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k != "total" and v},
        "model_flops": mf,
        "useful_flop_ratio": mf / jc.flops if jc.flops else 0.0,
        "compute_s": terms["compute_s"],
        "memory_s": terms["memory_s"],
        "collective_s": terms["collective_s"],
        "bottleneck": terms["bottleneck"].replace("_s", ""),
        "xla_flops_per_device": ca.get("flops", 0.0),
        "xla_bytes_per_device": ca.get("bytes accessed", 0.0),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "compile_seconds": round(time.time() - t0, 1),
        "ok": True,
    }
    if verbose:
        print(f"== {arch} × {shape_name} × {record['mesh']} (fsdp={use_fsdp}) ==")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops/device={ca.get('flops', 0):.3e} "
              f"bytes/device={ca.get('bytes accessed', 0):.3e}")
        print(f"  roofline: compute={terms['compute_s']:.4f}s memory={terms['memory_s']:.4f}s "
              f"collective={terms['collective_s']:.4f}s -> {record['bottleneck']}-bound")
        print(f"  model/HLO flop ratio: {record['useful_flop_ratio']:.3f} "
              f"(compile {record['compile_seconds']}s)")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=[*SHAPES, "all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--fsdp", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable), e.g. --set remat_policy=dots")
    ap.add_argument("--tag", default="", help="label for §Perf iteration records")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args(argv)
    overrides = dict(_parse_override(kv) for kv in args.set)

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                try:
                    rec = run_one(arch, shape_name, multi_pod=mp, fsdp=args.fsdp,
                                  remat=not args.no_remat, microbatches=args.microbatches,
                                  overrides=overrides, tag=args.tag)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "ok": False, "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
