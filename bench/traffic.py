"""Traffic. A mix is a data file, ``bench/traffic/<mix>.json``, of
parameters; its ``process`` names the code that turns them into
requests, ``bench/traffic/<process>.py``, found by that name. A new
process is a new file; a new mix of an existing process is data alone.

An open-loop process exposes ``arrivals(mix, seed, seconds)``: sorted
due times, in seconds from the window's start, over ``[0, seconds)``. A
closed-loop one exposes what its system asks of it (``closed_waves``:
``prompts``).

The processes copy the program's generators (``repro.core.workload``)
line for line, so that later changes to the program cannot move the
yardstick. What is shared between them lives here: the seeding and the
homogeneous Poisson times.
"""
from __future__ import annotations

import functools
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent / "traffic"


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any size of integer) and a sub-stream."""
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


def jax_seed(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for ``jax.random`` drawn from ``seed``."""
    return int(rng_for(seed, 1000 + stream).integers(0, 1 << 31))


@functools.lru_cache(maxsize=None)
def _load(name: str):
    from bench.harness import load_module
    return load_module(HERE / f"{name}.py", f"bench_traffic_{name}")


def process(mix: dict):
    """The module of the mix's process."""
    return _load(mix["process"])


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times of an open-loop mix (see the module's docstring)."""
    return np.sort(np.asarray(process(mix).arrivals(mix, seed, seconds),
                              np.float64))


def homogeneous_times(rng: np.random.Generator, lam: float,
                      horizon: float, t0: float = 0.0) -> np.ndarray:
    """Event times of a homogeneous Poisson(lam) process on
    [t0, t0+horizon), as ``repro.core.workload._homogeneous_times``
    draws them (chunked exponential gaps)."""
    if lam <= 0.0 or horizon <= 0.0:
        return np.empty(0)
    scale = 1.0 / lam
    end = t0 + horizon
    out = []
    t = t0
    chunk = max(256, int(lam * horizon * 1.1) + 16)
    while True:
        gaps = rng.exponential(scale, size=chunk)
        ts = np.cumsum(np.concatenate(([t], gaps)))[1:]
        if ts[-1] >= end:
            out.append(ts[ts < end])
            break
        out.append(ts)
        t = float(ts[-1])
        chunk = max(256, int((end - t) * lam * 1.2) + 16)
    return np.concatenate(out) if len(out) > 1 else out[0]
