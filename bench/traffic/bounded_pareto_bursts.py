"""A Poisson baseline at ``base_lam`` with burst episodes, as
``repro.core.workload.bounded_pareto_bursts`` draws it: bursts start as
a Poisson process of ``burst_rate`` per second; each multiplies the
rate by a bounded-Pareto(``pareto_alpha``, ``burst_lo``, ``burst_hi``)
factor for ``burst_duration`` seconds; where bursts overlap the largest
factor holds. Arrivals are thinned from a Poisson stream at the highest
possible rate. The number of bursts, their factors and times all come
from the seed."""
import heapq

import numpy as np

from bench import traffic


def bounded_pareto(rng, alpha: float, lo: float, hi: float,
                   size: int = 1) -> np.ndarray:
    """Bounded-Pareto(alpha, lo, hi) via inverse-CDF sampling."""
    u = rng.uniform(size=size)
    la, ha = lo ** alpha, hi ** alpha
    return (-(u * ha - u * la - ha) / (ha * la)) ** (-1.0 / alpha)


def burst_envelope(starts, factors, duration: float):
    """(bounds, seg_max): on [bounds[i], bounds[i+1]) the largest active
    factor is seg_max[i + 1]; seg_max[0] = 1.0 covers t < bounds[0]."""
    events = sorted(
        [(float(s), 0, float(f)) for s, f in zip(starts, factors)]
        + [(float(s) + duration, 1, float(f))
           for s, f in zip(starts, factors)])
    bounds, seg_max = [], [1.0]
    heap: list = []          # negated active factors
    removed: dict = {}       # lazy deletions
    i = 0
    while i < len(events):
        t = events[i][0]
        while i < len(events) and events[i][0] == t:
            _, kind, f = events[i]
            if kind == 0:
                heapq.heappush(heap, -f)
            else:
                removed[f] = removed.get(f, 0) + 1
            i += 1
        while heap and removed.get(-heap[0], 0) > 0:
            removed[-heap[0]] -= 1
            heapq.heappop(heap)
        bounds.append(t)
        seg_max.append(max(1.0, -heap[0]) if heap else 1.0)
    return np.asarray(bounds), np.asarray(seg_max)


def arrivals(mix: dict, seed: int, seconds: float):
    rng = traffic.rng_for(seed, 0)
    base, hi = float(mix["base_lam"]), float(mix["burst_hi"])
    starts = traffic.homogeneous_times(rng, float(mix["burst_rate"]),
                                       seconds)
    factors = bounded_pareto(rng, float(mix["pareto_alpha"]),
                             float(mix["burst_lo"]), hi, size=starts.size)
    lam_max = base * hi
    cands = traffic.homogeneous_times(rng, lam_max, seconds)
    if starts.size == 0:
        rate = np.full(cands.shape, base)
    else:
        bounds, seg_max = burst_envelope(starts, factors,
                                         float(mix["burst_duration"]))
        rate = base * seg_max[np.searchsorted(bounds, cands, side="right")]
    if cands.size == 0:
        return cands
    u = rng.uniform(size=cands.size)
    return cands[u <= rate / lam_max]
