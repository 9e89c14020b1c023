"""Whole-policy routing decisions as single fused kernels (ISSUE 9).

``routing_score`` fused score+select for ``route_best``; the other three
registered strategies still pulled the full (R, I) score matrix to the
host and decided in Python. These kernels move each policy's COMPLETE
decision onto the device:

* :func:`routing_guard` — score every candidate, gather the home
  column, apply the paper's Algorithm-1 guard ``(g_home - rtt_home) >
  tau -> upstream`` per request, and emit ``(chosen_idx, g, offloaded)``
  in one launch (the ``guarded_alg1`` strategy);
* :func:`routing_topk` — the route_best primary (SLO filter + latency
  argmin + two-stage cost tie-break) plus the next ``k - 1`` feasible
  candidates in ascending-g order with the f32-pinned first-occurrence
  tie-break, optionally headroom-gated (``g <= slo - margin``) — the
  ``safetail`` redundant dispatch;
* :func:`routing_attain` — primary = argmax of the delivery-weighted
  SLO-attainment probability ``(1 - loss) * Phi((ln slo - ln g) /
  sigma*sqrt2)`` with ties (within an absolute 1e-6 attainment band)
  breaking toward lower g then lower index, plus the same headroom-gated
  duplicate columns — the ``reliable`` strategy.

Scoring is shared with ``routing_score`` (whose kernel body and launch
live here too): the closed-form latency law plus hat-function
interpolation of the precomputed per-deployment Erlang-C wait table
(``build_erlang_table``), so the whole candidate table stays
VMEM-resident and a window of R decisions is one launch.

TPU layout: every block is rank 2 (see :func:`_launch`), so windows of
several ``block_r`` blocks compile, and the in-kernel argmin / one-hot
/ any are spelled as masked min/sum reductions over an integer column
iota, which Mosaic lowers. The attainment kernel's ``erf`` is the f32
rational approximation in ``ref.erf``, shared with its oracle.

Guard arithmetic is shared: :func:`apply_guard` is the single guard
surface consumed by the kernel here, by ``guarded.decide``'s fused
path, and by ``jaxsim``'s per-bucket windowed routing — the scan twin
and the event loop cannot drift on Algorithm 1.

Oracles: ``repro.kernels.ref.routing_guard`` / ``ref.routing_topk`` /
``ref.routing_attain``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.router import BIG as UNSTABLE_G   # 1e9 unstable sentinel
from repro.kernels.ref import erf

BIG = 1e30          # masking constant for argmin keys
_SQRT2 = 1.4142135623730951
ATTAIN_BAND = 1e-6  # absolute attainment tie band (f32-pinned semantics)


def apply_guard(g_home, rtt_home, tau, up, has_up, home):  # laimr-lint: disable=kernel-oracle -- shared guard arithmetic, not a kernel: the Pallas guard kernel, guarded.decide's vmap reference and jaxsim's scan twin all consume it, and every routing_guard parity sweep exercises it
    """Algorithm-1 offload guard, the ONE shared surface.

    ``g_home`` is the home pool's predicted latency with the vmap
    scorer's unstable sentinel (``router.BIG``); the guard compares the
    *controllable* part (RTT stripped, except for the sentinel which
    must stay above any tau) against the budget and routes at-risk
    requests one hop up. Returns ``(target, offloaded)``.
    """
    g_inst = jnp.where(g_home < jnp.float32(UNSTABLE_G),
                       g_home - rtt_home, g_home)
    off = (g_inst > tau) & has_up
    target = jnp.where(off, up, home)
    return target, off


def _col_index(shape) -> jax.Array:
    """Candidate-column index over an (R, I) block. Built from an
    integer iota: Mosaic has no float iota."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _argmin_cols(key):
    """Row-wise first-occurrence argmin as an (R, 1) int32 column —
    ``jnp.argmin(key, axis=1)`` for finite keys, spelled as a masked
    min over the column index (f32 indices are exact below 2**24)."""
    col = _col_index(key.shape).astype(jnp.float32)
    kmin = jnp.min(key, axis=1, keepdims=True)
    first = jnp.min(jnp.where(key == kmin, col, jnp.float32(key.shape[1])),
                    axis=1, keepdims=True)
    return first.astype(jnp.int32)


def _pick(values, idx):
    """values[r, idx[r]] as an (R, 1) column: a one-hot contraction, no
    gather. ``idx`` is an (R, 1) int32 column."""
    onehot = (_col_index(values.shape) == idx).astype(jnp.float32)
    return jnp.sum(values * onehot, axis=1, keepdims=True)


def _any_cols(mask):
    """Row-wise ``any`` as an (R, 1) bool column (f32 max reduction)."""
    return jnp.max(mask.astype(jnp.float32), axis=1, keepdims=True) > 0.0


def _scores(lam, alpha, beta, gamma, mu, n, rtt, table):
    """(g, rho) over the (R, I) block: pow via exp/log, Erlang-C wait
    via a hat-function weighted contraction against the (I, T) table.
    ``lam`` is (R, I) or an (R, 1) column; candidate params are (1, I)
    rows."""
    t = table.shape[1]
    lam_tilde = lam / jnp.maximum(n, 1.0)
    proc = alpha + beta * jnp.exp(
        gamma * jnp.log(jnp.maximum(lam_tilde, 1e-20)))
    proc = jnp.where(lam_tilde > 0.0, proc, alpha)
    rho = lam / jnp.maximum(n * mu, 1e-12)
    pos = jnp.clip(rho, 0.0, 1.0) * (t - 1)
    grid = jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2).astype(
        jnp.float32)
    w = jnp.maximum(0.0, 1.0 - jnp.abs(pos[:, :, None] - grid))
    q = jnp.sum(w * table[None, :, :], axis=2)
    return proc + rtt + q, rho


def _pack(cols):
    """(R, 1) columns -> one (R, k) block, by lane selects (no concat)."""
    shape = (cols[0].shape[0], len(cols))
    ci = _col_index(shape)
    out = jnp.broadcast_to(cols[0], shape)
    for j in range(1, len(cols)):
        out = jnp.where(ci == j, cols[j], out)
    return out


def _row_params(lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref,
                rtt_ref, table_ref):
    return (lam_ref[...].astype(jnp.float32), alpha_ref[...],
            beta_ref[...], gamma_ref[...], mu_ref[...], n_ref[...],
            rtt_ref[...], table_ref[...])


def _guard_kernel(lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref,
                  rtt_ref, tau_ref, home_ref, up_ref, table_ref,
                  idx_ref, g_ref, off_ref):
    lam, alpha, beta, gamma, mu, n, rtt, table = _row_params(
        lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref, rtt_ref,
        table_ref)
    g, rho = _scores(lam, alpha, beta, gamma, mu, n, rtt, table)
    # the vmap scorer's sentinel for unstable pools — the guard (and the
    # predicted latency) must see exactly the value guarded.decide sees
    g_eff = jnp.where(rho < 1.0, g, jnp.float32(UNSTABLE_G))
    home = home_ref[...]
    up = up_ref[...]
    g_home = _pick(g_eff, home)
    rtt_home = _pick(jnp.broadcast_to(rtt, g.shape), home)
    target, off = apply_guard(g_home, rtt_home, tau_ref[...],
                              up, up >= 0, home)
    idx_ref[...] = target.astype(jnp.int32)
    g_ref[...] = _pick(g_eff, target)
    off_ref[...] = off.astype(jnp.int32)


def _primary_route_best(g, rho, slo, cost):
    """route_best's pinned two-stage selection over a scored block:
    feasibility, masked latency argmin with the 1e-5 near band, cost
    argmin among near-ties (first occurrence = stable by index).
    Returns (primary (R, 1), feasible (R, I))."""
    feasible = (rho < 1.0) & (g <= slo)
    g_masked = jnp.where(feasible, g, BIG)
    gmin = jnp.min(g_masked, axis=1, keepdims=True)
    near = feasible & (g_masked <= gmin * (1.0 + 1e-5) + 1e-9)
    key = jnp.where(near, cost, BIG)
    return _argmin_cols(key), feasible


def _score_kernel(lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref,
                  rtt_ref, slo_ref, cost_ref, table_ref,
                  idx_ref, g_ref, ok_ref):
    lam, alpha, beta, gamma, mu, n, rtt, table = _row_params(
        lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref, rtt_ref,
        table_ref)
    g, rho = _scores(lam, alpha, beta, gamma, mu, n, rtt, table)
    primary, feasible = _primary_route_best(g, rho, slo_ref[...],
                                            cost_ref[...])
    idx_ref[...] = primary
    g_ref[...] = _pick(g, primary)
    ok_ref[...] = _any_cols(feasible).astype(jnp.int32)


def _dup_columns(g, start_mask, k):
    """k - 1 duplicate columns by iterative masked argmin over
    ``start_mask`` — ascending g, ties to the lowest index (argmin's
    first occurrence, matching np.argsort(kind="stable"))."""
    col = _col_index(g.shape)
    remaining = start_mask
    cols, gcols = [], []
    for _ in range(k - 1):
        ij = _argmin_cols(jnp.where(remaining, g, BIG))
        has = _any_cols(remaining)
        jh = (col == ij) & has
        cols.append(jnp.where(has, ij, -1))
        gcols.append(jnp.sum(g * jh.astype(jnp.float32), axis=1,
                             keepdims=True))
        remaining = remaining & ~jh
    return cols, gcols


def _finish_topk(g, rho, feasible, primary, ok, gate, k,
                 idx_ref, g_ref, ok_ref):
    """Emit the (R, K) outputs shared by the topk/attain kernels."""
    g_eff = jnp.where(rho < 1.0, g, jnp.float32(UNSTABLE_G))
    # infeasible rows report the row-minimum score (the vmap policies'
    # ``predicted = min(g[r])`` fallback) in column 0
    g0 = jnp.where(ok, _pick(g, primary),
                   jnp.min(g_eff, axis=1, keepdims=True))
    idx0 = jnp.where(ok, primary, -1)
    not_primary = _col_index(g.shape) != primary
    cols, gcols = _dup_columns(g, feasible & gate & not_primary, k)
    idx_ref[...] = _pack([idx0] + cols)
    g_ref[...] = _pack([g0] + gcols)
    ok_ref[...] = ok.astype(jnp.int32)


def _topk_kernel(lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref,
                 rtt_ref, slo_ref, cost_ref, table_ref,
                 idx_ref, g_ref, ok_ref, *, k, margin):
    lam, alpha, beta, gamma, mu, n, rtt, table = _row_params(
        lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref, rtt_ref,
        table_ref)
    slo = slo_ref[...]
    g, rho = _scores(lam, alpha, beta, gamma, mu, n, rtt, table)
    primary, feasible = _primary_route_best(g, rho, slo, cost_ref[...])
    ok = _any_cols(feasible)
    gate = g <= slo - jnp.float32(margin)
    _finish_topk(g, rho, feasible, primary, ok, gate, k,
                 idx_ref, g_ref, ok_ref)


def _attain_kernel(lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref,
                   rtt_ref, slo_ref, sigma_ref, avail_ref, table_ref,
                   idx_ref, g_ref, ok_ref, *, k, margin):
    lam, alpha, beta, gamma, mu, n, rtt, table = _row_params(
        lam_ref, alpha_ref, beta_ref, gamma_ref, mu_ref, n_ref, rtt_ref,
        table_ref)
    slo = slo_ref[...]
    sigma = sigma_ref[...]
    avail = avail_ref[...]
    g, rho = _scores(lam, alpha, beta, gamma, mu, n, rtt, table)
    feasible = (rho < 1.0) & (g <= slo)
    # delivery-weighted attainment, f32 end to end (decision precision)
    z = (jnp.log(jnp.maximum(slo, 1e-20)) - jnp.log(jnp.maximum(g, 1e-20))
         ) / (jnp.maximum(sigma, 1e-20) * jnp.float32(_SQRT2))
    phi = 0.5 * (1.0 + erf(jnp.clip(z, -10.0, 10.0)))
    p = avail * jnp.where(sigma > 0.0, phi,
                          (g <= slo).astype(jnp.float32))
    p_masked = jnp.where(feasible, p, -1.0)
    pmax = jnp.max(p_masked, axis=1, keepdims=True)
    nearp = feasible & (p_masked >= pmax - jnp.float32(ATTAIN_BAND))
    primary = _argmin_cols(jnp.where(nearp, g, BIG))
    ok = _any_cols(feasible)
    gate = g <= slo - jnp.float32(margin)
    _finish_topk(g, rho, feasible, primary, ok, gate, k,
                 idx_ref, g_ref, ok_ref)


def _launch(kernel, lam, inputs, table, out_shapes, block_r, interpret):
    """Shared pallas_call assembly: grid over request blocks, the whole
    candidate table + Erlang table resident per block. ``inputs`` is a
    list of ``(array, kind)`` with kind "cand" (an (I,) column, resident
    in full) or "req" (per-request rows, blocked over R — (R,) or
    (R, I) by the array's ndim). ``out_shapes`` lists the public
    ``(shape, dtype)`` of each output.

    Every block is rank 2, so any ``block_r`` that is a multiple of 8
    (or the whole window) tiles legally on the TPU: per-request vectors
    travel as (R, 1) columns, candidate columns as (1, I) rows, and bool
    outputs as int32. The public 1-D/bool forms are restored here."""
    r = lam.shape[0]
    i, t = table.shape
    block_r = min(block_r, r)
    assert r % block_r == 0, (r, block_r)

    def req(arr):
        a = arr.reshape(r, 1) if arr.ndim == 1 else arr
        return a, pl.BlockSpec((block_r, a.shape[1]), lambda ir: (ir, 0))

    pairs = [req(lam)]
    for arr, kind in inputs:
        pairs.append((arr.reshape(1, i), pl.BlockSpec((1, i), lambda ir: (0, 0)))
                     if kind == "cand" else req(arr))
    pairs.append((table, pl.BlockSpec((i, t), lambda ir: (0, 0))))
    args, in_specs = zip(*pairs)
    out_specs, shapes = [], []
    for shape, dtype in out_shapes:
        w = shape[1] if len(shape) == 2 else 1
        out_specs.append(pl.BlockSpec((block_r, w), lambda ir: (ir, 0)))
        shapes.append(jax.ShapeDtypeStruct(
            (r, w), jnp.int32 if dtype == jnp.bool_ else dtype))
    outs = pl.pallas_call(
        kernel, grid=(r // block_r,), in_specs=list(in_specs),
        out_specs=out_specs, out_shape=shapes, interpret=interpret,
    )(*args)
    return tuple((o != 0 if dtype == jnp.bool_ else o).reshape(shape)
                 for o, (shape, dtype) in zip(outs, out_shapes))


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def routing_guard(lam, alpha, beta, gamma, mu, n, rtt, tau, home, up,
                  erlang_c_table, block_r: int = 256,
                  interpret: bool = False):
    """Fused Algorithm-1 guarded routing: score all candidates, apply
    the per-request home guard, pick home-or-upstream in one launch.

    lam: (R,) shared or (R, I) per-candidate rates; tau: (R,) f32 guard
    budgets (the home column of the SLO rows); home/up: (R,) int32 home
    column and its upstream column (-1 at the top tier). Returns
    ``(chosen_idx (R,) int32, g (R,) f32 at the chosen column with the
    unstable sentinel, offloaded (R,) bool)``.
    """
    r = lam.shape[0]
    cand = [(c, "cand") for c in (alpha, beta, gamma, mu, n, rtt)]
    return _launch(
        _guard_kernel, lam,
        cand + [(tau.astype(jnp.float32), "req"),
                (home.astype(jnp.int32), "req"),
                (up.astype(jnp.int32), "req")],
        erlang_c_table,
        [((r,), jnp.int32), ((r,), jnp.float32), ((r,), jnp.bool_)],
        block_r, interpret)


@functools.partial(jax.jit,
                   static_argnames=("k", "margin", "block_r", "interpret"))
def routing_topk(lam, alpha, beta, gamma, mu, n, rtt, slo, cost,
                 erlang_c_table, k: int = 2, margin: float = 0.0,
                 block_r: int = 256, interpret: bool = False):
    """Fused top-k select: the route_best primary in column 0 plus the
    next ``k - 1`` feasible candidates in ascending-g order (primary
    excluded, headroom-gated by ``g <= slo - margin``), -1 where fewer
    exist. slo: (I,) or per-request (R, I) with lane exclusions folded
    in as slo = -1. Returns ``(idx (R, k) int32, g (R, k) f32, ok (R,)
    bool)`` — column 0 of g is the row-min score on infeasible rows
    (the policies' predicted-latency fallback).
    """
    r = lam.shape[0]
    cand = [(c, "cand") for c in (alpha, beta, gamma, mu, n, rtt)]
    return _launch(
        functools.partial(_topk_kernel, k=k, margin=float(margin)),
        lam,
        cand + [(slo, "cand" if slo.ndim == 1 else "req"),
                (cost, "cand")],
        erlang_c_table,
        [((r, k), jnp.int32), ((r, k), jnp.float32), ((r,), jnp.bool_)],
        block_r, interpret)


@functools.partial(jax.jit,
                   static_argnames=("k", "margin", "block_r", "interpret"))
def routing_attain(lam, alpha, beta, gamma, mu, n, rtt, slo, sigma, avail,
                   erlang_c_table, k: int = 2, margin: float = 0.0,
                   block_r: int = 256, interpret: bool = False):
    """Fused attainment-argmax select for the ``reliable`` strategy:
    primary = argmax of ``avail * Phi((ln slo - ln g) / (sigma *
    sqrt2))`` among feasible candidates, ties within an absolute 1e-6
    attainment band breaking toward lower g then lower index; duplicate
    columns exactly as :func:`routing_topk`. sigma/avail: (I,)
    per-candidate dispersion and delivery probability. Returns
    ``(idx (R, k) int32, g (R, k) f32, ok (R,) bool)``.
    """
    r = lam.shape[0]
    cand = [(c, "cand") for c in (alpha, beta, gamma, mu, n, rtt)]
    return _launch(
        functools.partial(_attain_kernel, k=k, margin=float(margin)),
        lam,
        cand + [(slo, "cand" if slo.ndim == 1 else "req"),
                (sigma, "cand"), (avail, "cand")],
        erlang_c_table,
        [((r, k), jnp.int32), ((r, k), jnp.float32), ((r,), jnp.bool_)],
        block_r, interpret)
