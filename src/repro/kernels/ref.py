"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the semantics contract: each Pallas kernel's test sweeps shapes
and dtypes and asserts allclose against the function here. They are also
the default execution path on CPU (models call them through
``repro.kernels.ops``), since the Pallas TPU kernels only run in
interpret mode on this host.

Conventions: q/k/v are (B, S, H, D) ("BSHD"); GQA is expressed as
n_heads % n_kv_heads == 0 with kv tensors carrying n_kv heads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D) by head repetition (GQA)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0, softcap: float = 0.0,
              scale: float | None = None,
              segment_pos: jax.Array | None = None) -> jax.Array:
    """Full (quadratic) multi-head attention with GQA / sliding window /
    logit soft-capping. Oracle for ``flash_attention``.

    q: (B, Sq, H, D);  k, v: (B, Skv, Hkv, D). For self-attention during
    training/prefill Sq == Skv; ``causal`` masks j > i; ``window`` > 0
    additionally masks j <= i - window (sliding window, gemma2-style);
    ``softcap`` applies tanh capping to the logits (gemma2).
    ``segment_pos``: optional (B, Sq) absolute positions of the queries
    (defaults to arange; needed when Sq is a suffix of the kv sequence).
    """
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    scale = (d ** -0.5) if scale is None else scale

    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = _softcap(logits, softcap)

    if segment_pos is None:
        qpos = jnp.arange(sq)[None, :] + (skv - sq)   # suffix alignment
        qpos = jnp.broadcast_to(qpos, (b, sq))
    else:
        qpos = segment_pos
    kpos = jnp.arange(skv)
    mask = jnp.ones((b, sq, skv), bool)
    if causal:
        mask &= kpos[None, None, :] <= qpos[:, :, None]
    if window > 0:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_pos: jax.Array, q_pos: jax.Array, *,
                     window: int = 0, softcap: float = 0.0,
                     scale: float | None = None) -> jax.Array:
    """Single-token attention against a (possibly ring-buffered) KV cache.
    Oracle for ``decode_attention``.

    q: (B, H, D) — one new token per sequence.
    k_cache/v_cache: (B, C, Hkv, W), W >= D — C cache slots; only the
        first D lanes of a row are attended (the rest is padding).
    kv_pos: (B, C) int32 — absolute position held in each slot; negative
        means the slot has never been written.
    q_pos: (B,) int32 — the query's absolute position.
    Valid keys: kv_pos >= 0, kv_pos <= q_pos, and within the window if set.
    """
    b, h, d = q.shape
    _, c, hkv, _ = k_cache.shape
    k = _repeat_kv(k_cache[..., :d], h // hkv)
    v = _repeat_kv(v_cache[..., :d], h // hkv)
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = _softcap(logits, softcap)
    valid = (kv_pos >= 0) & (kv_pos <= q_pos[:, None])
    if window > 0:
        valid &= kv_pos > (q_pos[:, None] - window)
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_stacked(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, kv_pos: jax.Array,
                             q_pos: jax.Array, layer: jax.Array, *,
                             window: int = 0, softcap: float = 0.0,
                             scale: float | None = None) -> jax.Array:
    """``decode_attention`` against layer ``layer`` (an int32 scalar) of
    a layer-stacked cache: k_cache/v_cache (L, B, C, Hkv, W), kv_pos
    (L, B, C). Oracle for ``decode_attention_stacked``."""
    k, v, pos = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                 for a in (k_cache, v_cache, kv_pos))
    return decode_attention(q, k, v, pos, q_pos, window=window,
                            softcap=softcap, scale=scale)


def moe_gmm(x: jax.Array, w: jax.Array,
            group_sizes: jax.Array) -> jax.Array:
    """Grouped matmul: the rows of x (M, K) are sorted by group, the
    first ``group_sizes[0]`` belong to group 0 and so on; row r of the
    result is ``x[r] @ w[g(r)]`` for w (E, K, N), f32 accumulation, in
    x's dtype. Rows past ``sum(group_sizes)`` are zero. Oracle for
    ``moe_gmm``: every group's product over all rows, each row kept
    from its own group's."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(x.shape[0])

    def one(acc, scanned):
        start, end, we = scanned
        y = jnp.dot(x, we, preferred_element_type=jnp.float32)
        mine = (rows >= start) & (rows < end)
        return jnp.where(mine[:, None], y, acc), None

    acc = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    acc, _ = jax.lax.scan(one, acc, (starts, ends, w))
    return acc.astype(x.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d_skip: jax.Array,
             initial_state: jax.Array | None = None,
             return_final_state: bool = False):
    """Mamba-2 SSD (state-space dual) — sequential reference.

    x:  (B, L, H, P)   input heads
    dt: (B, L, H)      softplus-activated step sizes (>0)
    a:  (H,)           negative state decay (A = -exp(a_log) outside)
    b:  (B, L, G, N)   input projection (G groups, N state)
    c:  (B, L, G, N)   output projection
    d_skip: (H,)       skip connection
    h_t = exp(dt*a) * h_{t-1} + dt * x_t  b_t^T ;  y_t = c_t h_t + D x_t

    Sequential lax.scan over L — the oracle the chunked Pallas kernel must
    match. Heads are grouped: head h uses group h // (H // G).
    """
    bsz, L, H, P = x.shape
    _, _, G, N = b.shape
    rep = H // G
    b_h = jnp.repeat(b, rep, axis=2)   # (B, L, H, N)
    c_h = jnp.repeat(c, rep, axis=2)

    decay = jnp.exp(dt.astype(jnp.float32) * a.astype(jnp.float32))  # (B,L,H)
    xin = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]  # dt * x

    def step(h, inputs):
        dec_t, x_t, b_t, c_t = inputs
        # h: (B, H, P, N)
        h = h * dec_t[..., None, None] \
            + x_t[..., :, None] * b_t[..., None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, c_t)
        return h, y

    h0 = jnp.zeros((bsz, H, P, N), jnp.float32) if initial_state is None \
        else initial_state.astype(jnp.float32)
    xs = (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(xin, 1, 0),
          jnp.moveaxis(b_h.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c_h.astype(jnp.float32), 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B, L, H, P)
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :, None]
    y = y.astype(x.dtype)
    if return_final_state:
        return y, h_final
    return y


# unstable-pool sentinel the vmap scorer emits (== repro.core.router.BIG)
_UNSTABLE_G = 1e9

# XLA's f32 rational approximation of erf (numerator odd, denominator
# even in x, highest power first); |x| >= 4 is +-1 in f32.
_ERF_ALPHA = (-2.72614225801306e-10, 2.77068142495902e-08,
              -2.10102402082508e-06, -5.69250639462346e-05,
              -7.34990630326855e-04, -2.95459980854025e-03,
              -1.60960333262415e-02)
_ERF_BETA = (-1.45660718464996e-05, -2.13374055278905e-04,
             -1.68282697438203e-03, -7.37332916720468e-03,
             -1.42647390514189e-02)


def erf(x: jax.Array) -> jax.Array:
    """f32 erf from elementwise ops only, so Mosaic lowers it (it has
    no ``erf`` primitive). ``routing_attain`` and its oracle below both
    call this one function, which keeps them float-identical."""
    x = jnp.clip(x.astype(jnp.float32), -4.0, 4.0)
    x2 = x * x
    num = jnp.float32(_ERF_ALPHA[0])
    for c in _ERF_ALPHA[1:]:
        num = num * x2 + jnp.float32(c)
    den = jnp.float32(_ERF_BETA[0])
    for c in _ERF_BETA[1:]:
        den = den * x2 + jnp.float32(c)
    return x * num / den


def _table_scores(lam: jax.Array, alpha: jax.Array, beta: jax.Array,
                  gamma: jax.Array, mu: jax.Array, n: jax.Array,
                  rtt: jax.Array, erlang_c_table: jax.Array):
    """(g, rho) over the (R, I) decision matrix with Erlang-C queueing
    read from the precomputed table (gather + linear interpolation on
    the rho grid — the structural twin of the kernels' hat-function
    contraction). Shared by every routing oracle below."""
    T = erlang_c_table.shape[1]
    lam_ = lam.astype(jnp.float32)            # (R,) or per-candidate (R, I)
    if lam_.ndim == 1:
        lam_ = lam_[:, None]                                    # (R, 1)
    lam_tilde = lam_ / jnp.maximum(n[None, :], 1.0)
    proc = alpha[None, :] + beta[None, :] * jnp.power(
        jnp.maximum(lam_tilde, 0.0), gamma[None, :])
    rho = lam_ / jnp.maximum(n[None, :] * mu[None, :], 1e-12)   # (R, I)
    # table lookup with linear interpolation on the rho grid
    pos = jnp.clip(rho, 0.0, 1.0) * (T - 1)
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, T - 2)
    frac = pos - lo.astype(jnp.float32)
    tbl = erlang_c_table.astype(jnp.float32)
    # gather per (r, i): table[i, lo[r, i]]
    q_lo = jax.vmap(lambda l_row: tbl[jnp.arange(tbl.shape[0]), l_row])(lo)
    q_hi = jax.vmap(lambda l_row: tbl[jnp.arange(tbl.shape[0]), l_row + 1])(lo)
    q = q_lo * (1 - frac) + q_hi * frac
    return proc + rtt[None, :] + q, rho


def _slo_rows(slo: jax.Array) -> jax.Array:
    slo_ = slo.astype(jnp.float32)
    return slo_[None, :] if slo_.ndim == 1 else slo_


def routing_score(lam: jax.Array, alpha: jax.Array, beta: jax.Array,
                  gamma: jax.Array, mu: jax.Array, n: jax.Array,
                  rtt: jax.Array, slo: jax.Array, cost: jax.Array,
                  erlang_c_table: jax.Array):
    """Batched LA-IMR routing decision. Oracle for ``routing_score``.

    For each request r (arrival-rate estimate lam[r], shape (R,)) against
    I candidate deployments, compute g_mi(lam) = affine power law
    + RTT + Erlang-C queueing (via a precomputed table over a rho grid —
    the in-memory table of paper §IV-B step ii), mask infeasible
    (g > slo or rho >= 1), and return (best index, best g, feasible?).

    slo is (I,) — budgets shared across requests — or (R, I) per-request
    rows (explicit ``req.slo`` / quality-lane exclusions as slo = -1).
    erlang_c_table: (I, T) — per-deployment expected wait at rho grid
    points rho = linspace(0, 1, T) (last entries may be large/BIG).
    """
    slo_ = _slo_rows(slo)
    g, rho = _table_scores(lam, alpha, beta, gamma, mu, n, rtt,
                           erlang_c_table)
    feasible = (rho < 1.0) & (g <= slo_)
    g_masked = jnp.where(feasible, g, jnp.inf)
    gmin = jnp.min(g_masked, axis=1, keepdims=True)
    near = feasible & (g_masked <= gmin * (1.0 + 1e-5) + 1e-9)
    idx = jnp.argmin(jnp.where(near, cost[None, :], jnp.inf), axis=1)
    any_ok = jnp.any(feasible, axis=1)
    best_g = jnp.take_along_axis(g, idx[:, None], axis=1)[:, 0]
    return idx, best_g, any_ok


def routing_guard(lam: jax.Array, alpha: jax.Array, beta: jax.Array,
                  gamma: jax.Array, mu: jax.Array, n: jax.Array,
                  rtt: jax.Array, tau: jax.Array, home: jax.Array,
                  up: jax.Array, erlang_c_table: jax.Array):
    """Fused Algorithm-1 guarded routing. Oracle for ``routing_guard``.

    Scores every candidate, gathers the per-request home column, strips
    the home RTT from the controllable latency (except for the unstable
    sentinel, which must stay above any tau) and offloads one hop up
    when ``g_inst > tau`` and an upstream exists. tau: (R,) guard
    budgets; home/up: (R,) int columns (up = -1 at the top tier).
    Returns (chosen (R,) int32, g at chosen (R,), offloaded (R,) bool).
    """
    g, rho = _table_scores(lam, alpha, beta, gamma, mu, n, rtt,
                           erlang_c_table)
    g_eff = jnp.where(rho < 1.0, g, jnp.float32(_UNSTABLE_G))
    home_ = home.astype(jnp.int32)
    up_ = up.astype(jnp.int32)
    g_home = jnp.take_along_axis(g_eff, home_[:, None], axis=1)[:, 0]
    g_inst = jnp.where(g_home < jnp.float32(_UNSTABLE_G),
                       g_home - rtt[home_], g_home)
    off = (g_inst > tau.astype(jnp.float32)) & (up_ >= 0)
    chosen = jnp.where(off, up_, home_)
    g_sel = jnp.take_along_axis(g_eff, chosen[:, None], axis=1)[:, 0]
    return chosen.astype(jnp.int32), g_sel, off


def _dup_order(g: jax.Array, elig: jax.Array, ok: jax.Array, k: int):
    """k - 1 duplicate columns from a stable ascending-g argsort over
    the eligible set (ties to the lowest index) — the argsort twin of
    the kernels' iterative masked argmin."""
    order = jnp.argsort(jnp.where(elig, g, jnp.inf), axis=1)
    cnt = elig.sum(axis=1)
    cols, gcols = [], []
    for j in range(1, k):
        cj = order[:, j - 1]
        valid = ok & (j - 1 < cnt)
        cols.append(jnp.where(valid, cj, -1).astype(jnp.int32))
        gcols.append(jnp.where(
            valid, jnp.take_along_axis(g, cj[:, None], axis=1)[:, 0], 0.0))
    return cols, gcols


def _topk_outputs(g: jax.Array, rho: jax.Array, feasible: jax.Array,
                  primary: jax.Array, gate: jax.Array, k: int):
    ok = jnp.any(feasible, axis=1)
    g_eff = jnp.where(rho < 1.0, g, jnp.float32(_UNSTABLE_G))
    g_p = jnp.take_along_axis(g, primary[:, None], axis=1)[:, 0]
    idx0 = jnp.where(ok, primary, -1).astype(jnp.int32)
    g0 = jnp.where(ok, g_p, jnp.min(g_eff, axis=1))
    cols_i = jnp.arange(g.shape[1])[None, :]
    elig = feasible & gate & (cols_i != primary[:, None])
    cols, gcols = _dup_order(g, elig, ok, k)
    return (jnp.stack([idx0] + cols, axis=1),
            jnp.stack([g0] + gcols, axis=1), ok)


def routing_topk(lam: jax.Array, alpha: jax.Array, beta: jax.Array,
                 gamma: jax.Array, mu: jax.Array, n: jax.Array,
                 rtt: jax.Array, slo: jax.Array, cost: jax.Array,
                 erlang_c_table: jax.Array, k: int = 2,
                 margin: float = 0.0):
    """Fused top-k select. Oracle for ``routing_topk``.

    Column 0 is the route_best primary (SLO filter + latency argmin +
    two-stage cost tie-break); columns 1..k-1 are the next feasible
    candidates in ascending-g order, primary excluded and headroom-gated
    by ``g <= slo - margin``, with -1 where fewer exist. Infeasible rows
    report the row-min score in g column 0 (the predicted fallback).
    """
    slo_ = _slo_rows(slo)
    g, rho = _table_scores(lam, alpha, beta, gamma, mu, n, rtt,
                           erlang_c_table)
    feasible = (rho < 1.0) & (g <= slo_)
    g_masked = jnp.where(feasible, g, jnp.inf)
    gmin = jnp.min(g_masked, axis=1, keepdims=True)
    near = feasible & (g_masked <= gmin * (1.0 + 1e-5) + 1e-9)
    primary = jnp.argmin(jnp.where(near, cost[None, :], jnp.inf), axis=1)
    gate = g <= slo_ - jnp.float32(margin)
    return _topk_outputs(g, rho, feasible, primary, gate, k)


def routing_attain(lam: jax.Array, alpha: jax.Array, beta: jax.Array,
                   gamma: jax.Array, mu: jax.Array, n: jax.Array,
                   rtt: jax.Array, slo: jax.Array, sigma: jax.Array,
                   avail: jax.Array, erlang_c_table: jax.Array,
                   k: int = 2, margin: float = 0.0):
    """Fused attainment-argmax select. Oracle for ``routing_attain``.

    The primary maximises the delivery-weighted SLO-attainment
    probability ``avail * Phi((ln slo - ln g) / (sigma * sqrt2))`` over
    feasible candidates (f32 — the pinned decision precision); ties
    within an absolute 1e-6 attainment band break toward lower g then
    lower index, so the uniform-distribution case degrades to argmin g.
    Duplicate columns as in :func:`routing_topk`.
    """
    slo_ = _slo_rows(slo)
    g, rho = _table_scores(lam, alpha, beta, gamma, mu, n, rtt,
                           erlang_c_table)
    feasible = (rho < 1.0) & (g <= slo_)
    z = (jnp.log(jnp.maximum(slo_, 1e-20))
         - jnp.log(jnp.maximum(g, 1e-20))) \
        / (jnp.maximum(sigma[None, :], 1e-20)
           * jnp.float32(1.4142135623730951))
    phi = 0.5 * (1.0 + erf(jnp.clip(z, -10.0, 10.0)))
    p = avail[None, :] * jnp.where(sigma[None, :] > 0.0, phi,
                                   (g <= slo_).astype(jnp.float32))
    p_masked = jnp.where(feasible, p, -1.0)
    pmax = jnp.max(p_masked, axis=1, keepdims=True)
    nearp = feasible & (p_masked >= pmax - jnp.float32(1e-6))
    primary = jnp.argmin(jnp.where(nearp, g, jnp.inf), axis=1)
    gate = g <= slo_ - jnp.float32(margin)
    return _topk_outputs(g, rho, feasible, primary, gate, k)
