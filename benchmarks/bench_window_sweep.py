"""Tail-latency cost of admission-window width under burst (ISSUE 3).

  PYTHONPATH=src python -m benchmarks.bench_window_sweep \
      [--smoke] [--windows 0,0.05,0.2,0.5] [--seed 7]

The unified control plane lets the discrete-event simulator route
arrivals through the serving engine's admission windows
(``SimConfig.admission_window``): wider windows amortise the batched
scoring dispatch over more requests but decide on staler rate estimates
and hold requests longer. This sweep quantifies that trade-off — the
ROADMAP item "measure tail-latency impact of window width under burst"
— across three bursty scenarios:

  * ``flash``  — flash-crowd step (PM-HPA scale-out race);
  * ``mmpp``   — Markov-modulated Poisson (correlated burstiness);
  * ``pareto`` — bounded-Pareto burst intensities (heavy-tailed spikes).

Window 0 is the scalar per-arrival Algorithm-1 path (the golden-digest
reference); every width > 0 runs the shared
:class:`repro.control.plane.ControlPlane`. Reported per (scenario,
width): completions, P50/P99 latency, offload rate, window flushes.
``--smoke`` shrinks the sweep for CI (one burst scenario per generator,
two widths, short horizon).
"""
from __future__ import annotations

import argparse

from benchmarks.common import experiment_cluster, finite_row, \
    write_bench_json
from repro.core.simulator import ClusterSimulator, SimConfig
from repro.core.workload import (bounded_pareto_bursts, flash_crowd_arrivals,
                                 mmpp_arrivals)

SLO = 1.8
WINDOWS = (0.0, 0.05, 0.2, 0.5)
SMOKE_WINDOWS = (0.0, 0.2)


def scenarios(horizon: float, seed: int) -> dict[str, list]:
    return {
        "flash": flash_crowd_arrivals(2.0, 12.0, horizon, "yolov5m",
                                      seed=seed, t_start=horizon * 0.25,
                                      duration=horizon * 0.2, ramp=5.0),
        "mmpp": mmpp_arrivals([1.5, 10.0], horizon / 8.0, horizon,
                              "yolov5m", seed=seed),
        "pareto": bounded_pareto_bursts(3.0, horizon, "yolov5m", seed=seed),
    }


def run_cell(arrivals: list, window: float, seed: int) -> dict:
    sim = ClusterSimulator(
        experiment_cluster(),
        SimConfig(mode="laimr", seed=seed, slo=SLO, jitter_sigma=0.2,
                  admission_window=window))
    res = sim.run(arrivals, horizon=None)
    s = res.summary()
    return {
        "n": int(s["n"]) if s["n"] == s["n"] else 0,
        "p50": s["p50"], "p99": s["p99"],
        "offload_rate": res.offload_fast / max(len(arrivals), 1),
        "flushes": sim.plane.flushes if sim.plane is not None else 0,
    }


def main(print_csv: bool = True, smoke: bool = False, windows=None,
         seed: int = 7) -> dict:
    horizon = 60.0 if smoke else 240.0
    widths = tuple(windows) if windows is not None else \
        (SMOKE_WINDOWS if smoke else WINDOWS)
    traces = scenarios(horizon, seed)
    out: dict = {}
    if print_csv:
        print("# admission-window width sweep (laimr, unified control "
              "plane; window=0 = scalar Algorithm-1 path)")
        print("scenario,window_s,n,p50_s,p99_s,offload_rate,flushes")
    for name, arr in traces.items():
        for w in widths:
            row = run_cell(arr, w, seed)
            out[(name, w)] = row
            if not finite_row(row, f"window_sweep:{name}@{w}"):
                continue
            if print_csv:
                print(f"{name},{w},{row['n']},{row['p50']:.4f},"
                      f"{row['p99']:.4f},{row['offload_rate']:.3f},"
                      f"{row['flushes']}")
        # conservation is the smoke-level sanity bar: every arrival must
        # complete in every cell, or the windowed adapter dropped work.
        bad = [w for w in widths if out[(name, w)]["n"] != len(arr)]
        if bad:
            raise SystemExit(
                f"window sweep BROKE CONSERVATION: {name} windows {bad} "
                f"completed != {len(arr)} arrivals")
    if print_csv:
        print(f"# {len(traces)} bursty scenarios x {len(widths)} widths; "
              "conservation held in every cell")
    write_bench_json("window_sweep", {
        "slo": SLO, "seed": seed, "horizon": horizon, "smoke": smoke,
        "rows": [{"scenario": name, "window": w, **row}
                 for (name, w), row in out.items()]})
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short horizon + two widths (CI)")
    ap.add_argument("--windows", default=None,
                    help="comma-separated window widths in seconds")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    wins = [float(w) for w in args.windows.split(",")] \
        if args.windows else None
    main(smoke=args.smoke, windows=wins, seed=args.seed)
