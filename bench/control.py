"""Readings of a cell's control, the numbers its limits are set from.
Not part of any benchmark run. One process, on the chip:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

* Route cells: the run with the routing kernel replaced by the float64
  reference recomputed in bfloat16 (``bench/tests/route_faults.py``),
  at the cell's own load; prints each compared number.
* Served cells: a run of the program, then, over the same sample of
  served requests, the reference's gaps of the served tokens (the
  program's reading) and of the tokens an int8 (W8A8) copy of the
  reference puts first (the control's reading), each judged by the
  cell's own comparison (``serve.compare``).

Each line is JSON: ``{"seed", "program": {...}, "control": {...}}``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def _judged(checks: dict) -> dict:
    """The numbers of ``checks`` and ``correct`` as the harness finds it."""
    return {**{k: v for k, (v, _) in checks.items()},
            "correct": all(v <= lim for v, lim in checks.values())}


def route_control(cell, seed: int, seconds: float) -> dict:
    from bench.systems import route
    from bench.tests import route_faults
    run = harness.Run(cell, seed, seconds, False, time.perf_counter())
    out = route.run(run, replace=route_faults.control(cell.config))
    return {"control": _judged(out.checks)}


def serve_control(cell, seed: int, seconds: float) -> dict:
    """The program's run, then both readings over its sample."""
    from bench import traffic, weights
    from bench.systems import serve
    from bench.reference import stablelm
    run = harness.Run(cell, seed, seconds, False, time.perf_counter())
    out = serve.run(run)
    prompts, served, own = out.extra["sample"]
    params = weights.make(cell.config, traffic.jax_seed(seed))
    ctl = np.asarray(stablelm.int8_gaps(params, cell.config, prompts, served))
    del params

    def summary(g):
        return {"served_gap_mean": float(g.mean()),
                "served_gap_max": float(g.max()),
                "share_off_first": float((g > 0).mean())}
    return {"program": {**summary(own), **_judged(out.checks)},
            "control": {**summary(ctl), **_judged(
                serve.compare(ctl, cell.params["limits"]))},
            "tokens_per_s": out.metrics["served_tokens_per_s"]}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_cache()
    fn = {"route": route_control, "serve": serve_control}[
        cell.config["system"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed, **fn(cell, seed, args.seconds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
