import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# NOTE: the two lines above MUST run before any other import (jax locks
# the device count on first initialisation). Dry-run only — tests and
# benches see the real single CPU device.

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and record memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      --arch all --shape all --mesh single,multi --out results/dryrun

Each combo writes results/dryrun/<arch>__<shape>__<mesh>.json:
  status      ok | skip(reason) | error(message)
  memory      per-device bytes (argument/output/temp/generated code)
  flops       HLO total FLOPs (cost_analysis)
  hlo_bytes   HLO bytes accessed
  collectives per-op-kind operand bytes (parsed from optimized HLO)
  wall_s      lower+compile wall time
"""
import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, SHAPES, ArchConfig, get_config
from repro.distributed import sharding
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import make_mesh, make_production_mesh

# --------------------------------------------------------------- skips
LONG_OK = {"mamba2_370m", "recurrentgemma_2b", "gemma2_27b"}


def applicability(arch_id: str, shape_name: str) -> str | None:
    """Return a skip reason, or None if the pair must lower."""
    if shape_name == "long_500k":
        if arch_id == "whisper_small":
            return ("SKIP: enc-dec with full-attention encoder; 512k frames "
                    "is the quadratic regime long_500k excludes (DESIGN §4)")
        if arch_id not in LONG_OK:
            return ("SKIP: pure full-attention decoder; long_500k requires "
                    "sub-quadratic attention (DESIGN §4)")
    return None


def config_for(arch_id: str, shape_name: str) -> ArchConfig:
    if arch_id == "gemma2_27b" and shape_name == "long_500k":
        from repro.configs.gemma2_27b import CONFIG_SW
        return CONFIG_SW          # sliding-window variant (beyond-paper)
    return get_config(arch_id)


# ------------------------------------------------------------- dry run
def build_shardings(cfg: ArchConfig, shape, mesh, args):
    """in_shardings matching specs.step_fn_for's arg tuple."""
    fsdp_train = True
    fsdp_serve = cfg.serve_fsdp
    if shape.kind == "train":
        p, o, b = args
        return (sharding.params_sharding(p, mesh, fsdp=fsdp_train),
                sharding.opt_state_sharding(o, mesh, fsdp=fsdp_train),
                sharding.batch_sharding(b, mesh))
    if shape.kind == "prefill":
        p, b = args
        return (sharding.params_sharding(p, mesh, fsdp=fsdp_serve),
                sharding.batch_sharding(b, mesh))
    p, tokens, cache, pos = args
    long_ctx = shape.global_batch == 1
    return (sharding.params_sharding(p, mesh, fsdp=fsdp_serve),
            sharding.token_sharding(tokens.shape, mesh),
            sharding.cache_sharding(cache, mesh, cfg, long_context=long_ctx),
            sharding.token_sharding(pos.shape, mesh))


def run_one(arch_id: str, shape_name: str, mesh_kind: str,
            opts: tuple = (), mesh_shape: tuple | None = None) -> dict:
    rec: dict = {"arch": arch_id, "shape": shape_name, "mesh": mesh_kind,
                 "opts": list(opts)}
    reason = applicability(arch_id, shape_name)
    if reason:
        rec["status"] = "skip"
        rec["reason"] = reason
        return rec
    shape = SHAPES[shape_name]
    cfg = config_for(arch_id, shape_name)
    if mesh_shape is not None:
        rec["mesh_shape"] = list(mesh_shape)
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        fn, args = specs.step_fn_for(cfg, shape)
        in_sh = build_shardings(cfg, shape, mesh, args)
        # pin the residual stream's batch sharding (see sharding.py note);
        # long_500k has batch=1 and context-shards the cache instead.
        if shape.global_batch > 1:
            sharding.set_activation_batch_axes(sharding.batch_axes(mesh))
        else:
            sharding.set_activation_batch_axes(None)
        if opts and "moe" in opts:
            n_groups = int(np.prod([mesh.shape[a] for a in
                                    sharding.batch_axes(mesh)]))
            sharding.set_moe_expert_axis("model", groups=n_groups)
        try:
            with mesh:
                lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
                compiled = lowered.compile()
        finally:
            sharding.set_activation_batch_axes(None)
            sharding.set_moe_expert_axis(None, groups=1)
        rec["wall_s"] = round(time.time() - t0, 1)
        rec["status"] = "ok"
        rec["variant"] = cfg.name
        # ---- memory ----
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
            }
        except Exception as e:  # CPU backend may not implement it
            rec["memory"] = {"error": str(e)}
        # ---- XLA's own cost analysis (while bodies counted ONCE) ----
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            rec["xla_flops"] = float(ca.get("flops", -1.0))
            rec["xla_bytes"] = float(ca.get("bytes accessed", -1.0))
        except Exception as e:
            rec["cost_error"] = str(e)
        # ---- trip-count-aware analysis (repro.launch.hlo_analysis) ----
        try:
            txt = compiled.as_text()
        except Exception:
            txt = lowered.as_text()
        costs = hlo_analysis.analyze(txt)
        rec["flops"] = float(costs.flops)          # per-device, trip-aware
        rec["hlo_bytes"] = float(costs.bytes)      # HBM-traffic proxy
        rec["collectives"] = {k: int(v) for k, v in costs.collectives.items()}
        rec["collective_bytes_total"] = int(costs.collective_bytes)
        rec["n_devices"] = int(mesh.devices.size)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    help="comma list from {single,multi}")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true",
                    help="recompute existing results")
    ap.add_argument("--opt", default="",
                    help="comma list of optimisations, e.g. moe,fused_attn")
    ap.add_argument("--mesh-shape", default="",
                    help="override single-pod mesh, e.g. 32x8")
    args = ap.parse_args()
    opts = tuple(o for o in args.opt.split(",") if o)
    mesh_shape = tuple(int(x) for x in args.mesh_shape.split("x")) \
        if args.mesh_shape else None
    if "fused_attn" in opts:
        from repro.kernels import ops as _ops
        _ops.set_implementation("fused")

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")
    os.makedirs(args.out, exist_ok=True)

    for arch in archs:
        arch = arch.replace("-", "_")
        for shape in shapes:
            for mesh_kind in meshes:
                path = os.path.join(args.out,
                                    f"{arch}__{shape}__{mesh_kind}.json")
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        old = json.load(f)
                    print(f"[cached] {arch:20s} {shape:12s} {mesh_kind:6s} "
                          f"-> {old['status']}")
                    continue
                rec = run_one(arch, shape, mesh_kind, opts=opts,
                              mesh_shape=mesh_shape)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                extra = ""
                if rec["status"] == "ok":
                    gf = rec.get("flops", 0) / 1e12
                    cb = rec.get("collective_bytes_total", 0) / 1e9
                    extra = f"flops={gf:.1f}T coll={cb:.2f}GB " \
                            f"wall={rec['wall_s']}s"
                elif rec["status"] == "error":
                    extra = rec["error"][:120]
                print(f"[{rec['status']:5s}] {arch:20s} {shape:12s} "
                      f"{mesh_kind:6s} {extra}", flush=True)


if __name__ == "__main__":
    main()
