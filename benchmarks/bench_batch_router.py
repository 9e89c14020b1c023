"""Batched admission-window routing vs the scalar per-request loop.

  PYTHONPATH=src python -m benchmarks.bench_batch_router \
      [--batches 1,8,64,256] [--rounds 30] [--pallas] [--policy all]

Measures routing decisions/sec through three paths on the same two-tier
experiment cluster:

  * ``route_best``   — the scalar per-request serving path this PR
                       replaces: one jit scoring dispatch per request;
  * ``scalar_np``    — the numpy float64 per-request reference loop
                       (``route_window_scalar``): no jit dispatch, but
                       still one Erlang evaluation per (request,
                       candidate) pair in Python;
  * ``batched``      — the admission-window loop: ONE
                       ``score_instances_batch`` + ``select_instance_batch``
                       call per window of R requests.

The acceptance bar (ISSUE 2): batched >= 3x decisions/sec over the
scalar per-request loop at batch 64. ``--pallas`` adds the Pallas kernel
in interpret mode (semantics demo only — interpret mode is orders of
magnitude slower than compiled TPU execution).

``--policy`` (ISSUE 9) sweeps the registered window strategies through
three decision paths at batch 64:

  * ``scalar`` — the per-request score-matrix + Python-loop path: one
                 ``decide()`` (and hence one scoring dispatch) per
                 request;
  * ``vmap``   — one windowed ``decide()`` on the vmap fallback
                 (batched score matrix + host post-processing);
  * ``fused``  — one windowed ``decide()`` with ``backend="pallas"``:
                 the whole decision (guard / top-k / attainment select)
                 in a single fused launch.

The ISSUE 9 bar: fused >= 3x decisions/sec over the per-request
score-matrix + Python-loop path at batch 64 for ``guarded_alg1``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import experiment_cluster, write_bench_json
from repro.control.policies import make_policy
from repro.core.router import Router, RouterParams
from repro.core.scheduler import QualityClass, Request
from repro.serving.batch_router import (AdmissionConfig, BatchRouter,
                                        route_window_scalar)

POLICIES = ("route_best", "guarded_alg1", "safetail", "reliable")


def _mk_requests(n: int) -> list[Request]:
    return [Request(model="yolov5m", quality=QualityClass.BALANCED,
                    arrival=0.001 * k) for k in range(n)]


def _time(fn, rounds: int, warmup: int = 3) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(rounds):
        fn()
    return (time.perf_counter() - t0) / rounds


def _policy_rows(policies, rounds: int, batch: int = 64) -> dict:
    """Per-policy decisions/sec through the three decision paths.

    Every path gets a fresh policy + router on its own cluster so
    telemetry EWMAs and device-column caches never leak between
    timings. ``fused`` uses ``backend="pallas"`` — off-TPU the ops
    facade maps that to the jitted oracle, which is exactly the fused
    single-launch decision the policies ship on device."""
    rows: dict = {}
    for name in policies:
        row: dict = {}

        def _fresh(backend: str):
            cl = experiment_cluster()
            return make_policy(name, cl, Router(cl, RouterParams()),
                               AdmissionConfig(backend=backend,
                                               max_batch=batch))
        reqs = _mk_requests(batch)
        tick = [0.0]

        # score-matrix + Python-loop path: one decide() per request
        pol_s = _fresh("vmap")

        def scalar():
            tick[0] += 1.0
            for rq in reqs:
                pol_s.decide([rq], tick[0])
        dt = _time(scalar, max(rounds // 3, 5))
        row["scalar_dps"] = batch / dt

        # vmap fallback, one windowed decide()
        pol_v = _fresh("vmap")

        def vmapped():
            tick[0] += 1.0
            pol_v.decide(reqs, tick[0])
        dt = _time(vmapped, rounds)
        row["vmap_dps"] = batch / dt

        # fused decision kernel, one windowed decide()
        pol_f = _fresh("pallas")

        def fused():
            tick[0] += 1.0
            pol_f.decide(reqs, tick[0])
        dt = _time(fused, rounds)
        row["fused_dps"] = batch / dt

        row["fused_vs_scalar"] = row["fused_dps"] / row["scalar_dps"]
        row["fused_vs_vmap"] = row["fused_dps"] / row["vmap_dps"]
        rows[name] = row
    return rows


def main(print_csv: bool = True, batches=(1, 8, 64, 256),
         rounds: int = 30, pallas: bool = False,
         policies=POLICIES) -> dict:
    cluster = experiment_cluster()
    out: dict = {"batch": {}}

    # scalar per-request loop (the replaced serving path)
    router = Router(cluster, RouterParams())
    reqs = _mk_requests(64)
    tick = [0.0]

    def scalar_route_best():
        tick[0] += 1.0
        for rq in reqs:
            router.route_best(rq, tick[0])
    dt = _time(scalar_route_best, max(rounds // 3, 5))
    out["route_best_dps"] = len(reqs) / dt

    # numpy scalar reference window
    br_ref = BatchRouter(cluster)

    def scalar_np():
        route_window_scalar(br_ref, reqs, 1.0)
    dt = _time(scalar_np, rounds)
    out["scalar_np_dps"] = len(reqs) / dt

    # batched admission windows
    for b in batches:
        br = BatchRouter(cluster, config=AdmissionConfig(max_batch=b))
        window = _mk_requests(b)

        def batched():
            tick[0] += 1.0
            for rq in window:
                br.submit(rq, tick[0])
            br.flush(tick[0])
        dt = _time(batched, rounds)
        out["batch"][b] = b / dt

    if pallas:
        br_p = BatchRouter(cluster, config=AdmissionConfig(
            backend="pallas-interpret", max_batch=64, block_r=64))
        window = _mk_requests(64)

        def pallas_interp():
            tick[0] += 1.0
            for rq in window:
                br_p.submit(rq, tick[0])
            br_p.flush(tick[0])
        dt = _time(pallas_interp, max(rounds // 10, 2))
        out["pallas_interpret_dps"] = 64 / dt

    out["policy"] = _policy_rows(policies, rounds) if policies else {}

    if print_csv:
        print("# batched admission-window routing vs scalar loops")
        print("path,batch,decisions_per_s,speedup_vs_route_best")
        base = out["route_best_dps"]
        print(f"route_best,1,{base:.0f},1.00")
        print(f"scalar_np,1,{out['scalar_np_dps']:.0f},"
              f"{out['scalar_np_dps'] / base:.2f}")
        for b, dps in out["batch"].items():
            print(f"batched,{b},{dps:.0f},{dps / base:.2f}")
        if "pallas_interpret_dps" in out:
            print(f"pallas_interpret,64,{out['pallas_interpret_dps']:.0f},"
                  f"{out['pallas_interpret_dps'] / base:.2f}")
        b64 = out["batch"].get(64)
        if b64 is not None:
            ok = b64 >= 3.0 * base
            print(f"# batched@64 speedup {b64 / base:.1f}x vs scalar "
                  f"per-request loop (target >= 3x): {'PASS' if ok else 'FAIL'}")
        if out["policy"]:
            print("# fused policy decisions at batch 64 (ISSUE 9)")
            print("policy,scalar_dps,vmap_dps,fused_dps,"
                  "fused_vs_scalar,fused_vs_vmap")
            for name, row in out["policy"].items():
                print(f"{name},{row['scalar_dps']:.0f},"
                      f"{row['vmap_dps']:.0f},{row['fused_dps']:.0f},"
                      f"{row['fused_vs_scalar']:.2f},"
                      f"{row['fused_vs_vmap']:.2f}")
            ga = out["policy"].get("guarded_alg1")
            if ga is not None:
                ok = ga["fused_vs_scalar"] >= 3.0
                print(f"# guarded_alg1 fused@64 speedup "
                      f"{ga['fused_vs_scalar']:.1f}x vs score-matrix + "
                      f"Python-loop path (target >= 3x): "
                      f"{'PASS' if ok else 'FAIL'}")
    write_bench_json("batch_router", {
        "route_best_dps": out["route_best_dps"],
        "scalar_np_dps": out["scalar_np_dps"],
        "batch": {str(b): dps for b, dps in out["batch"].items()},
        "pallas_interpret_dps": out.get("pallas_interpret_dps"),
        "policy": out["policy"],
    })
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1,8,64,256")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--policy", default="all",
                    help="comma list of window strategies to sweep "
                         "through scalar/vmap/fused decision paths "
                         "('all', 'none', or e.g. 'guarded_alg1')")
    args = ap.parse_args()
    if args.policy == "all":
        pols = POLICIES
    elif args.policy == "none":
        pols = ()
    else:
        pols = tuple(args.policy.split(","))
        unknown = set(pols) - set(POLICIES)
        if unknown:
            ap.error(f"unknown --policy {sorted(unknown)}; "
                     f"choose from {POLICIES}")
    main(batches=[int(b) for b in args.batches.split(",")],
         rounds=args.rounds, pallas=args.pallas, policies=pols)
