"""Router decision latency (the paper's 'microseconds of access time,
millisecond-level responses' claim, §I).

Measures:
  * in-memory telemetry update (SLIDINGRATE + EWMA) — pure Python;
  * one full Algorithm-1 pass (numpy control path, as the simulator runs);
  * the batched jit scoring hot path (requests/s through score_instances);
  * the Pallas routing_score kernel in interpret mode (semantics check;
    the TPU target compiles the same kernel).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.catalogue import paper_cluster
from repro.core.router import Router, RouterParams, score_instances
from repro.core.scheduler import QualityClass, Request
from repro.core.telemetry import ModelTelemetry


def _time(fn, n: int, warmup: int = 3) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def main(print_csv: bool = True) -> dict:
    out = {}
    tel = ModelTelemetry.create()
    t = [0.0]

    def telemetry_update():
        t[0] += 0.01
        tel.on_arrival(t[0])
    out["telemetry_update_us"] = _time(telemetry_update, 20000)

    cl = paper_cluster()
    router = Router(cl, RouterParams())
    dep = cl["yolov5m@pi4-edge"]
    tt = [0.0]

    def alg1_pass():
        tt[0] += 0.25
        router.on_request(Request(model="yolov5m",
                                  quality=QualityClass.BALANCED,
                                  arrival=tt[0]), dep, tt[0])
    out["algorithm1_pass_us"] = _time(alg1_pass, 2000)

    # batched jit scoring: 1024 requests x 8 deployments per call
    k = 8
    rng = np.random.default_rng(0)
    args = [jnp.asarray(rng.uniform(0.2, 2.0, k), jnp.float32)
            for _ in range(6)]
    lam = jnp.asarray(rng.uniform(0, 8, 1024), jnp.float32)
    batched = jax.jit(jax.vmap(lambda l: score_instances(l, *args)))

    def scoring():
        batched(lam).block_until_ready()
    out["batched_scoring_us_per_call"] = _time(scoring, 200)
    out["scoring_ns_per_decision"] = out["batched_scoring_us_per_call"] \
        / 1024 * 1e3

    if print_csv:
        print("# router decision latency")
        print("metric,us")
        for kk, v in out.items():
            print(f"{kk},{v:.2f}")
        ok = out["algorithm1_pass_us"] < 1000.0
        print(f"# sub-millisecond Algorithm-1 pass: {ok} "
              "(paper: millisecond-level responses)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
