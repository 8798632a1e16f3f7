import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this AOT-compiles the real program (train_step for train
shapes, prefill for prefill shapes, serve/decode step for decode shapes)
against ShapeDtypeStruct stand-ins carrying the production shardings - no
arrays are allocated. Records memory_analysis / cost_analysis / parsed
collective bytes into a JSON cache (one file per cell) that
EXPERIMENTS.md's tables and the roofline analysis read.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k \
      --mesh single [--tag baseline] [--force] [--set remat=dots] ...
  python -m repro.launch.dryrun --all [--mesh both]
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, ShapeCell,  # noqa: E402
                           cell_is_applicable, get_config)
from repro.launch.mesh import (cache_specs, input_specs,  # noqa: E402
                               make_production_mesh)
from repro.models import build_model  # noqa: E402
from repro.optim import AdamWConfig, init_state  # noqa: E402
from repro.optim.schedules import constant  # noqa: E402
from repro.roofline import Roofline  # noqa: E402
from repro.roofline.hlo_parse import analyze_hlo  # noqa: E402
from repro.train import (TrainState, make_gspmd_train_step,  # noqa: E402
                         shardings_for_params)

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# Per-arch microbatch counts for train_4k (keep per-device activations in
# the v5e HBM budget; validated via memory_analysis).
TRAIN_MICROBATCHES = {
    "gemma3-27b": 8, "arctic-480b": 8, "phi3.5-moe-42b-a6.6b": 4,
    "rwkv6-7b": 4, "qwen3-1.7b": 2, "minicpm-2b": 2, "internlm2-1.8b": 2,
    "hymba-1.5b": 2, "whisper-base": 1, "qwen2-vl-2b": 2,
}

# Baseline remat policy for train cells: without remat, the backward pass
# stores every attention-probability block across the layer scan (TBs of
# HBM traffic + temp memory). Production systems remat by default at these
# scales; --set remat=none reproduces the unrematted variant (recorded as
# hillclimb iteration 0 in EXPERIMENTS.md SPerf).
TRAIN_REMAT_DEFAULT = "full"

# The chip the production mesh stands for (a key of roofline.PEAKS): the
# dry-run compiles on host devices, whose own kind says nothing.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _sds_like(shapes_tree, shardings_tree):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes_tree, shardings_tree)


def _replicated_sds(shapes_tree, mesh):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())),
        shapes_tree)


def _shard_count(sharding, shape) -> int:
    try:
        return int(np.prod([sharding.mesh.shape[a]
                            for axes in sharding.spec if axes
                            for a in ((axes,) if isinstance(axes, str)
                                      else axes)]))
    except Exception:
        return 1


def _tree_bytes_per_device(sds_tree) -> float:
    total = 0.0
    for leaf in jax.tree.leaves(sds_tree):
        nbytes = np.prod(leaf.shape) * leaf.dtype.itemsize
        total += nbytes / _shard_count(leaf.sharding, leaf.shape)
    return total


def build_cell_program(arch: str, shape: ShapeCell, mesh, cfg=None,
                       microbatches=None):
    """Returns (jitted_fn, args_sds, model_flops, extra_bytes_info)."""
    cfg = cfg or get_config(arch)
    model = build_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    opt_cfg = AdamWConfig(moment_dtype=cfg.moment_dtype)

    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pshard = shardings_for_params(params_shapes, cfg, mesh)
    params_sds = _sds_like(params_shapes, pshard)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(params_shapes))
    n_active = cfg.active_params if cfg.family == "moe" else n_params

    info = {"n_params": n_params, "n_active": n_active,
            "params_bytes_per_device": _tree_bytes_per_device(params_sds)}

    if shape.kind == "train":
        nm = microbatches or TRAIN_MICROBATCHES.get(arch, 1)
        step = make_gspmd_train_step(model, mesh, opt_cfg, constant(1e-4),
                                     num_microbatches=nm)
        opt_shapes = jax.eval_shape(lambda p: init_state(p, opt_cfg),
                                    params_sds)
        opt_sds = {
            "mu": _sds_like(opt_shapes["mu"], pshard),
            "nu": _sds_like(opt_shapes["nu"], pshard),
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        }
        state_sds = TrainState(params_sds, opt_sds,
                               jax.ShapeDtypeStruct((), jnp.int32))
        batch_sds = input_specs(arch, shape, mesh, cfg)
        info["opt_bytes_per_device"] = _tree_bytes_per_device(opt_sds)
        info["microbatches"] = nm
        # 6 N D for train (fwd+bwd), D = total tokens
        model_flops = 6.0 * n_active * B * S
        fn = jax.jit(step, donate_argnums=(0,))
        return fn, (state_sds, batch_sds), model_flops, info

    if shape.kind == "prefill":
        batch_sds = input_specs(arch, shape, mesh, cfg)
        fn = jax.jit(lambda p, b: model.prefill(p, b))
        model_flops = 2.0 * n_active * B * S
        return fn, (params_sds, batch_sds), model_flops, info

    # decode
    csds = cache_specs(cfg, mesh, B, S)
    info["cache_bytes_per_device"] = _tree_bytes_per_device(csds)
    tok = input_specs(arch, shape, mesh, cfg)
    fn = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos),
                 donate_argnums=(1,))
    model_flops = 2.0 * n_active * B * 1
    return fn, (params_sds, csds, tok["tokens"], tok["pos"]), \
        model_flops, info


def run_cell(arch: str, shape: ShapeCell, mesh_kind: str, tag="baseline",
             force=False, overrides=None, microbatches=None) -> dict:
    out_path = OUT_DIR / f"{arch}__{shape.name}__{mesh_kind}__{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    ok, why = cell_is_applicable(arch, shape)
    rec = {"arch": arch, "shape": shape.name, "mesh": mesh_kind,
           "tag": tag, "timestamp": time.time()}
    if not ok:
        rec.update({"status": "skipped", "reason": why})
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    cfg = get_config(arch)
    if shape.kind == "train" and cfg.family in ("dense", "moe", "vlm"):
        cfg = cfg.replace(remat=TRAIN_REMAT_DEFAULT)
    if overrides:
        cfg = cfg.replace(**overrides)
        rec["overrides"] = {k: str(v) for k, v in overrides.items()}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    from repro.models.shardctx import set_batch_axes
    set_batch_axes(tuple(a for a in ("pod", "data")
                         if a in mesh.axis_names))
    try:
        t0 = time.time()
        fn, args, model_flops, info = build_cell_program(
            arch, shape, mesh, cfg, microbatches)
        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        cost = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            cost = {k: float(v) for k, v in ca.items()
                    if isinstance(v, (int, float)) and not k.startswith("u")}
        except Exception as e:  # pragma: no cover
            rec["cost_analysis_error"] = str(e)

        mem = {}
        try:
            ma = compiled.memory_analysis()
            for field in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                if hasattr(ma, field):
                    mem[field] = int(getattr(ma, field))
        except Exception as e:  # pragma: no cover
            rec["memory_analysis_error"] = str(e)

        t0 = time.time()
        text = compiled.as_text()
        hlo = analyze_hlo(text)   # loop-trip-aware FLOPs/bytes/collectives
        t_parse = time.time() - t0

        roof = Roofline(
            flops=hlo.flops,
            bytes_hbm=hlo.hbm_bytes,
            bytes_collective=hlo.collective_bytes,
            model_flops=model_flops,
            chips=chips,
            device_kind=TARGET_DEVICE_KIND)

        rec.update({
            "status": "ok",
            "chips": chips,
            "cost_analysis_per_iter": cost,   # XLA's (loop bodies once)
            "memory_analysis": mem,
            "collectives": {
                "bytes_by_kind": hlo.collective_by_kind,
                "total_bytes": hlo.collective_bytes,
                "n_ops": hlo.n_collectives,
                "warnings": hlo.warnings[:10],
            },
            "trip_counts": {k: v for k, v in
                            sorted(hlo.trip_counts.items())[:40]},
            "roofline": roof.to_dict(),
            "info": info,
            "hlo_lines": len(text.splitlines()),
            "timings": {"lower_s": t_lower, "compile_s": t_compile,
                        "parse_s": t_parse},
        })
    except Exception as e:
        rec.update({"status": "error", "error": repr(e),
                    "traceback": traceback.format_exc()[-4000:]})
    finally:
        set_batch_axes(None)
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (hillclimbing)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("True", "False"):
            v = v == "True"
        overrides[k] = v

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = SHAPES if (args.all or not args.shape) else \
        [s for s in SHAPES if s.name == args.shape]

    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                t0 = time.time()
                rec = run_cell(arch, shape, mesh_kind, tag=args.tag,
                               force=args.force,
                               overrides=overrides or None,
                               microbatches=args.microbatches)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']} "
                             f"tc={r['t_compute_s']:.3e} "
                             f"tm={r['t_memory_s']:.3e} "
                             f"tcoll={r['t_collective_s']:.3e}")
                elif status == "error":
                    extra = rec.get("error", "")[:120]
                print(f"[{mesh_kind}] {arch} x {shape.name}: {status} "
                      f"({time.time() - t0:.1f}s) {extra}", flush=True)


if __name__ == "__main__":
    main()
