"""Homogeneous Poisson arrivals at ``lam`` requests/s, as
``repro.core.workload.poisson_arrivals`` draws them."""
from bench import traffic


def arrivals(mix: dict, seed: int, seconds: float):
    rng = traffic.rng_for(seed, 0)
    return traffic.homogeneous_times(rng, float(mix["lam"]), seconds)
