"""Benchmark runner: one module per paper table/figure + the roofline.

  PYTHONPATH=src python -m benchmarks.run [--only fig2,table6,...]

Prints each benchmark's CSV block; the roofline section is skipped
gracefully when results/dryrun has not been generated yet (run
``python -m repro.launch.dryrun`` first).
"""
from __future__ import annotations

import argparse
import os
import time

ALL = ("fig2", "table4", "fig3", "fig4", "table6", "batch_router",
       "window_sweep", "policy_matrix", "capacity", "sim_throughput",
       "roofline")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(ALL))
    args = ap.parse_args()
    wanted = [w.strip() for w in args.only.split(",") if w.strip()]

    for name in wanted:
        t0 = time.time()
        print(f"\n===== bench:{name} =====")
        try:
            if name == "fig2":
                from benchmarks import bench_fig2 as m
            elif name == "table4":
                from benchmarks import bench_table4 as m
            elif name == "fig3":
                from benchmarks import bench_fig3 as m
            elif name == "fig4":
                from benchmarks import bench_fig4 as m
            elif name == "table6":
                from benchmarks import bench_table6 as m
            elif name == "batch_router":
                from benchmarks import bench_batch_router as m
            elif name == "window_sweep":
                from benchmarks import bench_window_sweep as m
            elif name == "policy_matrix":
                from benchmarks import bench_policy_matrix as m
            elif name == "capacity":
                from benchmarks import bench_capacity as m
            elif name == "sim_throughput":
                from benchmarks import bench_sim_throughput as m
            elif name == "roofline":
                if not os.path.isdir("results/dryrun"):
                    print("# skipped: results/dryrun missing "
                          "(run python -m repro.launch.dryrun)")
                    continue
                from benchmarks import roofline as m
            else:
                print(f"# unknown benchmark {name}")
                continue
            m.main()
        except Exception as e:  # keep the harness running
            print(f"# bench:{name} FAILED: {type(e).__name__}: {e}")
        print(f"# bench:{name} wall={time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
