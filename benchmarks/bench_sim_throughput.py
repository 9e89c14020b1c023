"""Fleet-scale simulator throughput baseline: >=1M arrivals end-to-end.

  PYTHONPATH=src python -m benchmarks.bench_sim_throughput \
      [--arrivals 1000000] [--lam 2000] [--mode laimr,baseline] \
      [--backend event,jax] [--warmup 1] \
      [--scenario poisson|mixed|bursts|diurnal|flash|mmpp] [--seed 0]

Generates a >=1M-arrival trace, drives it through the discrete-event
simulator in each controller mode x backend, and reports events/sec —
the speed baseline every future PR is measured against. Reference
points on this trace shape (poisson, two-tier cluster, one CPU core):

  * seed implementation (pre fast-path):   ~2.0k laimr arrivals/s
  * fleet-scale fast path (PR 1):          >=5x that, same latencies
    bit-for-bit (tests/test_sim_golden.py pins the digests).
  * chunked JAX twin (--backend jax):      >=20x the event loop on the
    1M-arrival flash trace (observed ~55x warm), distribution-pinned
    within repro.core.jaxsim.TOLERANCES.

The trace is counted in *arrivals*; the simulator additionally processes
one service-end event per request plus replica-ready/HPA-tick events, so
events/sec is roughly 2x arrivals/sec (the jax backend reports the
comparable ``2 * arrivals + buckets`` accounting).

When both backends run in one invocation (``--backend event,jax``), the
event rows are the oracle: the jax rows are checked against them for
exact arrival conservation and P50/P99/offload-rate within the declared
TOLERANCES — a violation exits non-zero. Results land in
``results/bench/BENCH_sim_throughput.json`` via common.write_bench_json.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from benchmarks.common import write_bench_json

from repro.core.catalogue import Cluster, Deployment, paper_cluster
from repro.core.latency_model import CLOUD, PI4_EDGE, YOLOV5M
from repro.core.scheduler import QualityClass
from repro.core.simulator import ClusterSimulator, SimConfig
from repro.core.workload import (bounded_pareto_bursts, diurnal_arrivals,
                                 flash_crowd_arrivals, mixed_traffic,
                                 mmpp_arrivals, poisson_arrivals)


def fleet_cluster(n_edge: int = 16, n_cloud: int = 16) -> Cluster:
    """A two-tier pool sized for thousands of req/s so the event loop —
    not a pathological 1M-deep queue — is what gets measured."""
    edge = dataclasses.replace(PI4_EDGE, net_rtt=0.05, speedup=100.0,
                               r_max=300.0)
    cloud = dataclasses.replace(CLOUD, net_rtt=0.086, r_max=19000.0,
                                speedup=400.0)
    return Cluster([
        Deployment(YOLOV5M, edge, QualityClass.BALANCED,
                   n_replicas=n_edge, n_max=4 * n_edge),
        Deployment(YOLOV5M, cloud, QualityClass.BALANCED,
                   n_replicas=n_cloud, n_max=4 * n_cloud),
    ])


def make_trace(scenario: str, n_arrivals: int, lam: float, seed: int):
    horizon = max(n_arrivals / lam, 1.0)
    if scenario == "poisson":
        return poisson_arrivals(lam, horizon, "yolov5m", seed=seed)
    if scenario == "mixed":
        return mixed_traffic({"yolov5m": lam * 0.6, "efficientdet": lam * 0.3,
                              "faster_rcnn": lam * 0.1}, horizon, seed=seed)
    if scenario == "bursts":
        return bounded_pareto_bursts(lam / 2.0, horizon, "yolov5m",
                                     seed=seed, burst_hi=4.0)
    if scenario == "diurnal":
        return diurnal_arrivals(lam, horizon, "yolov5m", seed=seed,
                                amplitude=0.8,
                                period=max(horizon / 4.0, 1.0))
    if scenario == "flash":
        return flash_crowd_arrivals(lam * 0.5, lam * 2.0, horizon,
                                    "yolov5m", seed=seed,
                                    t_start=horizon * 0.4,
                                    duration=horizon * 0.2,
                                    ramp=horizon * 0.02)
    if scenario == "mmpp":
        return mmpp_arrivals([lam * 0.5, lam * 2.0],
                             max(horizon / 20.0, 1.0), horizon,
                             "yolov5m", seed=seed)
    raise SystemExit(f"unknown scenario {scenario!r}")


def run_once(cluster_fn, mode: str, backend: str, arr, seed: int,
             warmup: int) -> dict:
    """One timed (mode, backend) row. The jax backend jit-compiles on
    first use, so ``warmup`` untimed full passes run first (same shapes
    -> the timed pass hits the jit cache); the event loop gets none."""
    cfg = SimConfig(mode=mode, seed=seed, backend=backend)
    if backend == "jax":
        for _ in range(max(0, warmup)):
            ClusterSimulator(cluster_fn(), cfg).run(arr)
    sim = ClusterSimulator(cluster_fn(), cfg)
    t0 = time.perf_counter()
    res = sim.run(arr)
    dt = time.perf_counter() - t0
    s = res.summary()
    n = len(arr)
    if backend == "jax":
        completed = res.n_arrivals - res.failed_count()
        conserved = res.n_arrivals == n
    else:
        completed = len(res.completed)
        conserved = len(res.completed) + len(res.failed) == n
    return {
        "mode": mode, "backend": backend, "arrivals": n,
        "completed": completed, "events": res.n_events, "wall_s": dt,
        "arrivals_per_s": n / dt, "events_per_s": res.n_events / dt,
        "p50_s": s["p50"], "p99_s": s["p99"], "failed": int(s["failed"]),
        "offload_rate": res.offload_fast / max(n, 1),
        "conserved": bool(conserved),
    }


def check_equivalence(oracle: dict, twin: dict) -> list[str]:
    """Distribution-equivalence violations of a jax row vs its event
    oracle row (same mode/trace), per repro.core.jaxsim.TOLERANCES."""
    from repro.core.jaxsim import TOLERANCES

    errs = []
    if not twin["conserved"]:
        errs.append(f"conservation: {twin['completed']} + "
                    f"{twin['failed']} != {twin['arrivals']}")
    for key, tol in (("p50_s", TOLERANCES["p50_rel"]),
                     ("p99_s", TOLERANCES["p99_rel"])):
        ref = oracle[key]
        if np.isfinite(ref) and ref > 0:
            rel = abs(twin[key] - ref) / ref
            if rel > tol:
                errs.append(f"{key}: {twin[key]:.4f} vs oracle "
                            f"{ref:.4f} (rel {rel:.3f} > {tol})")
    d_off = abs(twin["offload_rate"] - oracle["offload_rate"])
    if d_off > TOLERANCES["offload_abs"]:
        errs.append(f"offload_rate: {twin['offload_rate']:.4f} vs "
                    f"oracle {oracle['offload_rate']:.4f} "
                    f"(abs {d_off:.3f} > {TOLERANCES['offload_abs']})")
    return errs


def main(arrivals: int = 1_000_000, lam: float = 2000.0,
         mode: str = "laimr,baseline", backend: str = "event",
         warmup: int = 1, scenario: str = "poisson",
         seed: int = 0) -> None:
    backends = [b.strip() for b in backend.split(",") if b.strip()]
    for b in backends:
        if b not in ("event", "jax"):
            raise SystemExit(f"unknown backend {b!r} (event|jax)")

    t0 = time.perf_counter()
    arr = make_trace(scenario, arrivals, lam, seed)
    gen_dt = time.perf_counter() - t0
    print(f"scenario={scenario} arrivals={len(arr)} "
          f"gen_wall={gen_dt:.2f}s gen_rate={len(arr) / gen_dt:.0f}/s")

    cluster_fn = paper_cluster if scenario == "mixed" else fleet_cluster
    rows = []
    print("mode,backend,arrivals,completed,events,wall_s,arrivals_per_s,"
          "events_per_s,p50_s,p99_s,offload_rate")
    for md in [m.strip() for m in mode.split(",") if m.strip()]:
        if md not in ("laimr", "baseline"):
            raise SystemExit(f"unknown mode {md!r} (laimr|baseline)")
        for bk in backends:
            if bk == "jax" and md != "laimr":
                print(f"# skip: backend=jax supports mode=laimr only "
                      f"(asked for {md})")
                continue
            row = run_once(cluster_fn, md, bk, arr, seed, warmup)
            rows.append(row)
            # empty traces yield NaN percentiles — print them as 'nan'
            # but warn loudly rather than letting NaN slip into tables
            if not np.isfinite(row["p50_s"]):
                print(f"# WARNING[sim_throughput]: {md}/{bk} "
                      "completed no requests — percentiles undefined")
            print(f"{md},{bk},{row['arrivals']},{row['completed']},"
                  f"{row['events']},{row['wall_s']:.2f},"
                  f"{row['arrivals_per_s']:.0f},{row['events_per_s']:.0f},"
                  f"{row['p50_s']:.4f},{row['p99_s']:.4f},"
                  f"{row['offload_rate']:.4f}")

    # event rows are the oracle: pin jax speedup + distribution match
    failures = []
    by = {(r["mode"], r["backend"]): r for r in rows}
    for md in ("laimr",):
        oracle, twin = by.get((md, "event")), by.get((md, "jax"))
        if oracle is None or twin is None:
            continue
        speedup = twin["events_per_s"] / max(oracle["events_per_s"], 1e-9)
        twin["speedup_vs_event"] = speedup
        errs = check_equivalence(oracle, twin)
        status = "PASS" if not errs else "FAIL"
        print(f"# equivalence[{md}]: {status} speedup={speedup:.1f}x "
              f"dp50={abs(twin['p50_s'] - oracle['p50_s']):.4f}s "
              f"dp99={abs(twin['p99_s'] - oracle['p99_s']):.4f}s")
        for e in errs:
            print(f"#   {e}")
        failures.extend(errs)

    write_bench_json("sim_throughput", {
        "scenario": scenario, "lam": lam, "seed": seed,
        "warmup": warmup, "rows": rows,
    })
    if failures:
        raise SystemExit("sim_throughput: jax/event equivalence FAILED")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arrivals", type=int, default=1_000_000)
    ap.add_argument("--lam", type=float, default=2000.0)
    ap.add_argument("--mode", default="laimr,baseline")
    ap.add_argument("--backend", default="event",
                    help="comma list of event|jax (jax is laimr-only)")
    ap.add_argument("--warmup", type=int, default=1,
                    help="untimed jit-warming passes for the jax backend")
    ap.add_argument("--scenario", default="poisson")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    main(arrivals=a.arrivals, lam=a.lam, mode=a.mode, backend=a.backend,
         warmup=a.warmup, scenario=a.scenario, seed=a.seed)
