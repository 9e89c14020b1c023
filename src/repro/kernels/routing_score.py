"""Batched LA-IMR routing decisions as a single VMEM-resident kernel.

The paper's §IV-B hot path: for each incoming request, evaluate the
closed-form latency law g_mi(lambda) over every candidate deployment,
filter by SLO + stability, and argmin with a cost tie-break — 'in
microseconds, from in-process memory'. On TPU the whole instance table
(I deployments x a handful of f32 scalars + an (I, T) Erlang-C wait
table) is a few KB: it fits VMEM permanently, so a batch of R routing
decisions is ONE kernel launch with zero HBM traffic for the table.

TPU adaptation notes:
* The Erlang-C M/M/c wait has no closed form a VPU likes (factorials /
  iterative recurrences), so the control plane precomputes a per-
  deployment wait table over a rho grid (the paper's 'in-memory table
  ... refreshed every Delta seconds', §IV-B step ii) and the kernel does
  linear interpolation — expressed as a hat-function weighted matmul
  against the table (one (R,T) x (T,) contraction per deployment row)
  rather than a gather, because TPU vector gathers are the one thing
  this memory system hates.
* Tie-break-by-cost argmin is fused: key = (is_feasible, g, cost)
  lexicographic via masked min.
* The kernel body and the TPU block layout are shared with the
  whole-policy kernels in ``routing_decide`` (``_score_kernel``,
  ``_launch``).

Oracle: ``repro.kernels.ref.routing_score``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.routing_decide import _launch, _score_kernel


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def routing_score(lam, alpha, beta, gamma, mu, n, rtt, slo, cost,
                  erlang_c_table, block_r: int = 256,
                  interpret: bool = False):
    """lam: per-request arrival-rate estimates — (R,) to score every
    candidate at the same aggregate rate, or (R, I) with a per-candidate
    rate per request (the admission-window form, where each pool is
    scored at its own observed rate). slo: per-deployment budgets (I,)
    shared across requests, or per-request rows (R, I) — the explicit
    ``req.slo`` / quality-lane form (a lane exclusion is slo = -1: g is
    non-negative, so the candidate is infeasible exactly like the vmap
    path's candidate mask). Other per-deployment params (I,);
    erlang_c_table: (I, T) precomputed waits over a rho grid.
    Returns (idx (R,), best_g (R,), feasible (R,))."""
    r = lam.shape[0]
    cand = [(c, "cand") for c in (alpha, beta, gamma, mu, n, rtt)]
    return _launch(
        _score_kernel, lam,
        cand + [(slo, "cand" if slo.ndim == 1 else "req"), (cost, "cand")],
        erlang_c_table,
        [((r,), jnp.int32), ((r,), jnp.float32), ((r,), jnp.bool_)],
        block_r, interpret)


def build_erlang_table(mu, n, t: int = 65):  # laimr-lint: disable=kernel-oracle -- shared table builder, not a kernel: both routing_score paths (Pallas and ref.py) consume its output, and the kernel-vs-oracle sweeps in test_kernels exercise it on every case
    """Per-deployment M/M/c wait over rho = linspace(0, 1, t) — the
    'in-memory table pre-computed by the analytic model' (§IV-B)."""
    import numpy as np

    from repro.core import queueing
    mu = np.asarray(mu, np.float64)
    n = np.asarray(n, np.int64)
    rho = np.linspace(0.0, 1.0, t)
    out = np.zeros((len(mu), t), np.float32)
    for ii in range(len(mu)):
        lam = rho * n[ii] * mu[ii]
        for jj in range(t):
            w = queueing.mmc_wait_np(float(lam[jj]), np.array([n[ii]]),
                                     float(mu[ii]))[0]
            out[ii, jj] = min(float(w), 1e6) if np.isfinite(w) else 1e6
    return jnp.asarray(out)
