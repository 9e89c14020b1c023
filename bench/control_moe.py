"""Readings of the served MoE cell's control, the numbers its limit is
set from. Not part of any benchmark run. One process, on the chip:

    python3 bench/control_moe.py --workload mellum2_moe.code_decode \\
        --seeds 1,2,3 --seconds 5

For each seed: a run of the program (``bench/systems/serve_moe.py``),
then, over the same sample of served requests, the reference's gaps of
the served tokens (the program's reading) and of the tokens an int8
(W8A8, router at bf16) copy of the reference puts first (the control's
reading), each judged by the cell's own comparison (``serve.compare``).

Each line is JSON: ``{"seed", "program": {...}, "control": {...},
"tokens_per_s"}``.
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


def _summary(g: np.ndarray) -> dict:
    return {"served_gap_mean": float(g.mean()),
            "served_gap_max": float(g.max()),
            "share_off_first": float((g > 0).mean())}


def serve_moe_control(cell, seed: int, seconds: float) -> dict:
    """The program's run, then both readings over its sample."""
    from bench import traffic, weights_moe
    from bench.reference import mellum2
    from bench.systems import serve, serve_moe
    run = harness.Run(cell, seed, seconds, False, time.perf_counter())
    out = serve_moe.run(run)
    prompts, served, own = out.extra["sample"]
    params = weights_moe.make(cell.config, traffic.jax_seed(seed))
    ctl = np.asarray(mellum2.int8_gaps(params, cell.config, prompts, served))
    del params
    limits = cell.params["limits"]
    return {"program": {**_summary(own),
                        **{k: v for k, (v, _) in out.checks.items()},
                        "correct": all(v <= lim for v, lim
                                       in out.checks.values())},
            "control": {**_summary(ctl), "correct": all(
                v <= lim for v, lim in serve.compare(ctl, limits).values())},
            "tokens_per_s": out.metrics["served_tokens_per_s"]}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed, **serve_moe_control(
            cell, seed, args.seconds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
