"""Find the knee of a route cell: the highest steady Poisson rate at which
the decision backlog does not grow. One process, on the chip:

    python3 bench/sweep.py --workload paper_testbed.burst_route \
        --rates 1000,2000,4000 --seconds 5 [--seed 1]

For each rate it drives the cell's plane through ``bench/systems/route``
with a Poisson mix of that rate and prints one JSON line: decision P50
and P99, the generator's lag (mean over the first and the last fifth of
the requests, P99 and max), requests per flush and whether every
decision matched the reference. The backlog grows where the lag of the
last fifth is well above that of the first fifth. The knee is measured
once, when a cell is defined; its mix file then holds a fixed rate.
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
from bench.systems import route  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        mix = {"process": "poisson", "lam": rate,
               "model": cell.traffic["model"],
               "quality": cell.traffic["quality"]}
        run = harness.Run(cell, args.seed, args.seconds, False,
                          time.perf_counter())
        out = route.run(run, mix=mix)
        lag = np.asarray(run.spans.samples["submit_lag_ms"])
        fifth = max(1, lag.size // 5)
        print(json.dumps({
            "rate": rate, "requests": out.attempted, "failed": out.failed,
            **out.metrics,
            "lag_first_ms": float(lag[:fifth].mean()),
            "lag_last_ms": float(lag[-fifth:].mean()),
            "lag_p99_ms": float(np.percentile(lag, 99)),
            "lag_max_ms": float(lag.max()),
            "per_flush": out.extra["decided"] / max(1, out.extra["flushes"]),
            "flush_ms": run.spans.total("flush") * 1e3
            / max(1, out.extra["flushes"]),
            "correct": all(v <= lim for v, lim in out.checks.values())}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
