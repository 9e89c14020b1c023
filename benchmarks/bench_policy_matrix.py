"""Policy x burst-scenario x window x PODS x PLACEMENT P99 matrix
(ISSUE 4 + 5 + 10).

  PYTHONPATH=src python -m benchmarks.bench_policy_matrix \
      [--smoke] [--policies route_best,guarded_alg1,safetail,hybrid] \
      [--windows 0.05,0.2] [--pods 1,2,4] \
      [--placement first_fit,jsq] [--seed 7]

The pluggable policy layer lets the SAME discrete-event substrate answer
the paper-adjacent question the ROADMAP kept open: which *decision rule*
inside the control loop cuts the tail? Every registered strategy runs
under every burst scenario of the window sweep —

  * ``flash``  — flash-crowd step (PM-HPA scale-out race);
  * ``mmpp``   — Markov-modulated Poisson (correlated burstiness);
  * ``pareto`` — bounded-Pareto burst intensities (heavy-tailed spikes);

at each admission-window width AND each pod granularity
(``SimConfig.pods_per_deployment``, ISSUE 5): pods=1 is the legacy
monolithic pool, pods>1 splits every deployment into whole pods with
first-fit spillover, per-pod utilisation, pod-granular scale-out boot
lag and emptiest-pod drain — the regime where pod rounding and boot
chunking reshape the tail. The ``--placement`` axis (ISSUE 10) re-runs
every pods>1 cell under ``jsq`` placement (join-shortest-queue
admission, cold-pod duplicate pinning, finish-time work stealing and
replica-quota scale-out), recording the pods-regression repair next to
the first-fit baseline. Reported per cell: completions, P50/P99
latency, offload rate, duplicate rate (SafeTail redundancy), pods
booted/drained. The generalised conservation contract — every arrival
completes exactly once, plane outcomes ``admitted + offloaded +
rejected == arrivals`` with duplicates ledgered separately — is
ENFORCED in every cell; a violation aborts the bench.

A dedicated ``paper3`` section evaluates SafeTail on the THREE-TIER
``paper_cluster`` catalogue (ROADMAP open item: feasible alternates are
scarce on the two-tier experiment cluster), recording duplicate rate vs
pod count in the BENCH JSON. ``--smoke`` shrinks everything for CI.

``--faults`` switches to the chaos matrix (ISSUE 6): every policy runs
under seeded fault plans — ``none`` / ``crash`` (edge pods hard-killed
mid-burst) / ``straggle`` (an edge pod serves 4x slow for a window) /
``drop`` (lossy cloud uplink) — and each cell reports the
SLO-attainment rate plus failed/retried/fault counts next to the
percentiles. Conservation generalises per cell to ``completed + failed
== arrivals`` and the plane ledger's ``admitted + offloaded + rejected
+ failed == arrivals``; a violation still aborts the bench. The rows
land in a separate ``BENCH_policy_matrix_faults.json`` so the fault
axis never clobbers the main matrix artifact.

Results land in ``BENCH_policy_matrix.json``
(:func:`benchmarks.common.write_bench_json`) and are uploaded as a CI
artifact, so the policy/pods P99 trajectory is captured per-PR.
"""
from __future__ import annotations

import argparse

from benchmarks.bench_window_sweep import scenarios
from benchmarks.common import experiment_cluster, finite_row, \
    write_bench_json
from repro.core.catalogue import paper_cluster
from repro.core.simulator import ClusterSimulator, FaultPlan, PodCrash, \
    SimConfig, Straggler
from repro.core.workload import mixed_traffic

SLO = 1.8
POLICIES = ("route_best", "guarded_alg1", "safetail", "reliable",
            "hybrid")
# policies the chunked JAX twin models (repro.core.jaxsim scope)
JAX_POLICIES = ("route_best", "guarded_alg1")
WINDOWS = (0.05, 0.2)
SMOKE_WINDOWS = (0.1,)
PODS = (1, 2, 4)
SMOKE_PODS = (1, 2)
# pod-placement modes (ISSUE 10): first_fit is the digest-pinned
# default; jsq is the pods-regression repair (join-shortest-queue,
# cold-pod duplicates, work stealing, replica-quota scale-out). pods=1
# cells run first_fit only — placement is vacuous on a monolithic pool.
PLACEMENTS = ("first_fit", "jsq")


def run_cell(arrivals: list, policy: str, window: float, seed: int,
             pods: int = 1, redundancy: int = 2, cluster=None,
             label: str = "", slo: float = SLO,
             faults: FaultPlan = None, backend: str = "event",
             placement: str = "first_fit") -> dict:
    faults = faults if faults is not None else FaultPlan()
    sim = ClusterSimulator(
        cluster if cluster is not None else experiment_cluster(),
        SimConfig(mode="laimr", seed=seed, slo=slo, jitter_sigma=0.2,
                  admission_window=window, policy=policy,
                  redundancy=redundancy, pods_per_deployment=pods,
                  faults=faults, backend=backend, placement=placement))
    res = sim.run(arrivals, horizon=None)
    n_arr = len(arrivals)
    if backend == "jax":
        # The chunked twin has no control-plane ledger (routing happens
        # inside the scan); conservation is SimResult-count based: one
        # latency sample per arrival, none failed (empty FaultPlan).
        where = label or f"{policy}@{window}/pods={pods}/jax"
        if res.n_arrivals != n_arr or res.failed_count() != 0:
            raise SystemExit(
                f"policy matrix BROKE CONSERVATION: {where}: "
                f"{res.n_arrivals} samples ({res.failed_count()} failed) "
                f"!= {n_arr} arrivals")
        s = res.summary()
        return {
            "n": int(s["n"]) if s["n"] == s["n"] else 0,
            "p50": s["p50"], "p99": s["p99"],
            "offload_rate": res.offload_fast / n_arr,
            "duplicate_rate": 0.0, "dup_cancelled": 0, "flushes": 0,
            "pods_booted": res.pods_booted,
            "pods_drained": res.pods_drained,
            "slo_attain": res.slo_attainment(slo),
            **res.fault_counts(),
        }
    # generalised conservation, enforced per cell (now per pod count too;
    # under fault injection FAILED is a terminal outcome, so the invariant
    # is completed + failed == arrivals — with no faults failed must be 0
    # and the check collapses to the strict completed == arrivals)
    where = label or f"{policy}@{window}/pods={pods}"
    n_failed = len(res.failed)
    if faults.empty() and n_failed:
        raise SystemExit(
            f"policy matrix BROKE CONSERVATION: {where}: "
            f"{n_failed} failures with an empty FaultPlan")
    if len(res.completed) + n_failed != n_arr:
        raise SystemExit(
            f"policy matrix BROKE CONSERVATION: {where}: "
            f"{len(res.completed)} completed + {n_failed} failed "
            f"!= {n_arr} arrivals")
    sim.plane.check_conservation()
    if sim.plane.decided != n_arr:
        raise SystemExit(
            f"policy matrix BROKE CONSERVATION: {where}: "
            f"{sim.plane.decided} decided != {n_arr} arrivals")
    s = res.summary()
    out = sim.plane.outcomes
    return {
        "n": int(s["n"]) if s["n"] == s["n"] else 0,
        "p50": s["p50"], "p99": s["p99"],
        "offload_rate": out["offloaded"] / n_arr,
        "duplicate_rate": res.duplicates / n_arr,
        "dup_cancelled": res.dup_cancelled,
        "flushes": sim.plane.flushes,
        "pods_booted": res.pods_booted,
        "pods_drained": res.pods_drained,
        "slo_attain": res.slo_attainment(slo),
        **res.fault_counts(),
    }


# SafeTail needs >= 2 SLO-feasible candidates in a lane before it can
# duplicate. On the paper's 3-tier catalogue the BALANCED lane is
# yolov5m@edge + yolov5m@cloud, and the Pi-4 edge tier under burst sits
# around ~2-3 s predicted latency — at the 1.8 s experiment SLO it is
# almost never feasible, so redundancy still starves (duplicate rate
# ~0, the same scarcity the ROADMAP flagged on the two-tier cluster).
# 3.0 s gives the loaded edge tier headroom to stay feasible, which is
# the regime SafeTail's redundancy actually targets.
PAPER3_SLO = 3.0


def paper3_safetail_rows(horizon: float, seed: int, pod_counts,
                         print_csv: bool) -> list[dict]:
    """SafeTail on the paper's 3-tier catalogue: duplicate rate vs pod
    count (the two-tier cluster starves redundancy of feasible
    alternates under saturation — ROADMAP open item)."""
    arr = mixed_traffic({"efficientdet": 4.0, "yolov5m": 3.0,
                         "faster_rcnn": 1.0}, horizon, seed=seed)
    rows = []
    for pods in pod_counts:
        row = run_cell(arr, "safetail", 0.1, seed, pods=pods,
                       cluster=paper_cluster(), slo=PAPER3_SLO,
                       label=f"paper3:safetail/pods={pods}")
        rows.append({"policy": "safetail", "scenario": "paper3",
                     "window": 0.1, "pods": pods, **row})
        if finite_row(row, f"policy_matrix:paper3:safetail/pods={pods}") \
                and print_csv:
            print(f"safetail,paper3,0.1,{pods},{row['n']},"
                  f"{row['p50']:.4f},{row['p99']:.4f},"
                  f"{row['offload_rate']:.3f},"
                  f"{row['duplicate_rate']:.3f},{row['flushes']}")
    return rows


# Chaos matrix (ISSUE 6). The fault cells run at the paper3 headroom
# SLO: at 1.8 s the loaded Pi-4 edge tier is borderline-infeasible even
# before a crash, so every policy collapses to the same cloud offload
# and the fault axis measures nothing. 3.0 s keeps both tiers feasible,
# which is the regime where recovery STRATEGY (duplicate into headroom
# vs retry after the crash) separates the policies.
FAULT_SLO = PAPER3_SLO
FAULT_SCENARIOS = ("none", "crash", "straggle", "drop")
EDGE_KEY = "yolov5m@pi4-edge"


def fault_plans(horizon: float, seed: int) -> dict[str, FaultPlan]:
    """Seeded fault plans scaled to the bench horizon: an edge pod is
    hard-killed twice mid-trace (replacement boots after the configured
    startup delay), an edge pod straggles at 4x for the middle of the
    run, and the cloud uplink drops 20% of offloaded requests."""
    return {
        "none": FaultPlan(seed=seed),
        "crash": FaultPlan(crashes=(
            PodCrash(t=0.3 * horizon, dep_key=EDGE_KEY),
            PodCrash(t=0.6 * horizon, dep_key=EDGE_KEY)), seed=seed),
        "straggle": FaultPlan(stragglers=(
            Straggler(t_start=0.25 * horizon, t_end=0.75 * horizon,
                      dep_key=EDGE_KEY, factor=4.0),), seed=seed),
        "drop": FaultPlan(drop_prob={"cloud": 0.2}, seed=seed),
    }


def faults_main(print_csv: bool = True, smoke: bool = False,
                policies=None, seed: int = 7) -> list[dict]:
    """Policy x fault-plan chaos matrix on the two-tier experiment
    cluster (pods=2 so a crash kills a POD, not the whole tier)."""
    horizon = 60.0 if smoke else 240.0
    pols = tuple(policies) if policies is not None else POLICIES
    arr = scenarios(horizon, seed)["pareto"]
    plans = fault_plans(horizon, seed)
    rows = []
    attain: dict[tuple[str, str], float] = {}
    if print_csv:
        print("# policy x fault plan (pareto bursts, pods=2, "
              f"slo={FAULT_SLO}; conservation completed + failed == "
              "arrivals enforced per cell)")
        print("policy,faults,n,failed,retried,crashes,drops,straggled,"
              "slo_attain,p50_s,p99_s,duplicate_rate")
    for pol in pols:
        for fname in FAULT_SCENARIOS:
            row = run_cell(arr, pol, 0.1, seed, pods=2, slo=FAULT_SLO,
                           faults=plans[fname],
                           label=f"faults:{pol}/{fname}")
            rows.append({"policy": pol, "faults": fname,
                         "window": 0.1, "pods": 2, **row})
            attain[(pol, fname)] = row["slo_attain"]
            if not finite_row(row, f"policy_matrix_faults:{pol}/{fname}"):
                continue
            if print_csv:
                print(f"{pol},{fname},{row['n']},{row['failed']},"
                      f"{row['retried']},{row['crashes']},{row['drops']},"
                      f"{row['straggled']},{row['slo_attain']:.4f},"
                      f"{row['p50']:.4f},{row['p99']:.4f},"
                      f"{row['duplicate_rate']:.3f}")
    if print_csv and ("reliable", "crash") in attain \
            and ("route_best", "crash") in attain:
        rel, base = attain[("reliable", "crash")], \
            attain[("route_best", "crash")]
        verdict = "BEATS" if rel > base else "DOES NOT BEAT"
        print(f"# crash scenario: reliable slo_attain={rel:.4f} "
              f"{verdict} route_best slo_attain={base:.4f}")
    write_bench_json("policy_matrix_faults", {
        "slo": FAULT_SLO, "seed": seed, "horizon": horizon,
        "smoke": smoke, "pods": 2, "rows": rows})
    return rows


def main(print_csv: bool = True, smoke: bool = False, policies=None,
         windows=None, pods=None, seed: int = 7,
         backend: str = "event", placements=None) -> dict:
    horizon = 60.0 if smoke else 240.0
    pols = tuple(policies) if policies is not None else POLICIES
    if backend == "jax":
        # the chunked twin models route_best/guarded_alg1 only (no
        # redundant dispatch, no burst detector) — repro.core.jaxsim
        dropped = [p for p in pols if p not in JAX_POLICIES]
        pols = tuple(p for p in pols if p in JAX_POLICIES)
        if dropped and print_csv:
            print(f"# backend=jax: skipping unsupported policies "
                  f"{','.join(dropped)}")
    widths = tuple(windows) if windows is not None else \
        (SMOKE_WINDOWS if smoke else WINDOWS)
    pod_counts = tuple(pods) if pods is not None else \
        (SMOKE_PODS if smoke else PODS)
    modes = tuple(placements) if placements is not None else PLACEMENTS
    traces = scenarios(horizon, seed)
    out: dict = {}
    rows = []
    if print_csv:
        print("# policy x burst scenario x admission-window width x "
              f"pods x placement (laimr, unified control plane, "
              f"backend={backend}; conservation enforced per cell)")
        print("policy,scenario,window_s,pods,placement,n,p50_s,p99_s,"
              "offload_rate,duplicate_rate,flushes")
    for pol in pols:
        for name, arr in traces.items():
            for w in widths:
                for np_ in pod_counts:
                    for plc in modes:
                        if np_ == 1 and plc != "first_fit":
                            continue   # placement is vacuous on pods=1
                        row = run_cell(arr, pol, w, seed, pods=np_,
                                       backend=backend, placement=plc)
                        out[(pol, name, w, np_, plc)] = row
                        rows.append({"policy": pol, "scenario": name,
                                     "window": w, "pods": np_,
                                     "placement": plc,
                                     "backend": backend, **row})
                        if not finite_row(
                                row, f"policy_matrix:{pol}:{name}@{w}"
                                     f"/p{np_}/{plc}"):
                            continue
                        if print_csv:
                            print(f"{pol},{name},{w},{np_},{plc},"
                                  f"{row['n']},"
                                  f"{row['p50']:.4f},{row['p99']:.4f},"
                                  f"{row['offload_rate']:.3f},"
                                  f"{row['duplicate_rate']:.3f},"
                                  f"{row['flushes']}")
    # SafeTail on the 3-tier paper catalogue: duplicate rate vs pods
    if "safetail" in pols:
        rows.extend(paper3_safetail_rows(horizon, seed, pod_counts,
                                         print_csv))
    # the pods-regression headline (ISSUE 10): flash P99, guarded_alg1,
    # monolithic vs pods=2 first_fit vs pods=2 jsq — the repair the
    # placement axis exists to demonstrate
    if print_csv and "guarded_alg1" in pols and "flash" in traces:
        for w in widths:
            mono = out.get(("guarded_alg1", "flash", w, 1, "first_fit"))
            ff = out.get(("guarded_alg1", "flash", w, 2, "first_fit"))
            jq = out.get(("guarded_alg1", "flash", w, 2, "jsq"))
            if mono and jq:
                verdict = "REPAIRED" if jq["p99"] <= mono["p99"] \
                    else "NOT REPAIRED"
                print(f"# pods regression @w={w}: flash guarded_alg1 "
                      f"P99 pods=1 {mono['p99']:.3f}s, pods=2 first_fit "
                      f"{ff['p99'] if ff else float('nan'):.3f}s, "
                      f"pods=2 jsq {jq['p99']:.3f}s -> {verdict}")
    if print_csv:
        print(f"# {len(pols)} policies x {len(traces)} bursty scenarios "
              f"x {len(widths)} widths x {len(pod_counts)} pod counts "
              f"x {len(modes)} placements (+ safetail paper3 rows); "
              f"conservation held in every cell")
    write_bench_json("policy_matrix", {
        "slo": SLO, "seed": seed, "horizon": horizon, "smoke": smoke,
        "backend": backend, "pod_counts": list(pod_counts),
        "placements": list(modes), "rows": rows})
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short horizon, one width, two pod counts (CI)")
    ap.add_argument("--policies", default=None,
                    help="comma-separated registry names")
    ap.add_argument("--windows", default=None,
                    help="comma-separated window widths in seconds")
    ap.add_argument("--pods", default=None,
                    help="comma-separated pods_per_deployment counts")
    ap.add_argument("--placement", default=None,
                    help="comma-separated placement modes "
                         "(first_fit,jsq); pods=1 cells always run "
                         "first_fit only")
    ap.add_argument("--backend", default="event",
                    choices=("event", "jax"),
                    help="simulator backend for the main matrix "
                         "(jax = chunked lax.scan twin, "
                         "route_best/guarded_alg1 only)")
    ap.add_argument("--faults", action="store_true",
                    help="run the chaos matrix (policy x fault plan) "
                         "instead of the burst/window/pods matrix")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    pol_arg = [p.strip() for p in args.policies.split(",")] \
        if args.policies else None
    if args.faults:
        if args.backend != "event":
            raise SystemExit("--faults requires --backend event (the "
                             "jax twin refuses fault plans)")
        faults_main(smoke=args.smoke, policies=pol_arg, seed=args.seed)
    else:
        main(smoke=args.smoke, policies=pol_arg,
             windows=[float(w) for w in args.windows.split(",")]
             if args.windows else None,
             pods=[int(p) for p in args.pods.split(",")]
             if args.pods else None,
             seed=args.seed, backend=args.backend,
             placements=[p.strip() for p in args.placement.split(",")]
             if args.placement else None)
