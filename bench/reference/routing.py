"""Plain reference of the guarded Algorithm-1 window decision (LA-IMR,
arXiv:2505.07417, Alg. 1 lines 8-11), written from the configuration
file alone. It imports nothing of the program.

For every request row r of a window and candidate deployment i:

    alpha_i = (L_m / S_i) * (1 + (B_i / Rmax_i) ** gamma)        Eq. 9
    beta_i  = (L_m / S_i) * (R_m / Rmax_i) ** gamma
    mu_i    = S_i / L_m
    proc    = alpha_i + beta_i * (lam[r, i] / n_i) ** gamma      Eq. 8
    rho     = lam[r, i] / (n_i * mu_i)
    q       = Erlang-C M/M/c wait, read from a table over rho in
              [0, 1] at ``points`` grid points with linear interpolation
              and capped at ``cap_s`` (the paper's in-memory table, §IV-B)
    g       = proc + rtt_i + q          (1e9 where rho >= 1: unstable)

The request stays at its home deployment (the edge deployment of its
model) unless its controllable latency ``g[home] - rtt[home]`` exceeds
its budget ``tau = x * L_m / S_home (+ rtt_home)``; then it goes one hop
up, to the cloud deployment of the same model.

``window_rates`` recomputes the rates ``lam`` themselves from the
closing times of the windows and the decisions taken in them.

``Reference(conf, dtype="float64")`` computes in float64. With
``dtype="bfloat16"`` every operation is rounded to bfloat16: that is the
control, one precision below the float32 that the configuration states.
"""
from __future__ import annotations

import math

import numpy as np

UNSTABLE = 1e9
# a few float32 ulps of a rate, relative (8 x 2**-24)
RATE_ULPS = 8 * 2.0 ** -24


def _rounder(dtype: str):
    if dtype == "float64":
        return lambda x: np.asarray(x, np.float64)
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16

    def q(x):
        return np.asarray(np.asarray(x, np.float64).astype(bf16), np.float64)
    return q


def erlang_c_wait(lam: float, c: int, mu: float) -> float:
    """Expected M/M/c queueing wait in float64; inf when unstable."""
    if lam <= 0.0:
        return 0.0
    a = lam / mu
    rho = a / c
    if rho >= 1.0:
        return math.inf
    b = 1.0                       # Erlang B by its recursion
    for k in range(1, c + 1):
        b = a * b / (k + a * b)
    pw = b / (1.0 - rho * (1.0 - b))
    return pw / (c * mu - lam)


class Reference:
    def __init__(self, conf: dict, dtype: str = "float64"):
        q = self.q = _rounder(dtype)
        deps = conf["deployments"]
        self.keys = [f"{d['model']['name']}@{d['instance']['name']}"
                     for d in deps]
        base = np.array([d["model"]["l_ref"] / d["instance"]["speedup"]
                         for d in deps])
        gamma = np.array([d["gamma"] for d in deps], np.float64)
        bg = np.array([d["instance"]["background"] / d["instance"]["r_max"]
                       for d in deps])
        dem = np.array([d["model"]["r_demand"] / d["instance"]["r_max"]
                        for d in deps])
        self.alpha = base * (1.0 + bg ** gamma)
        self.beta = base * dem ** gamma
        self.gamma = gamma
        self.mu = np.array([d["instance"]["speedup"] / d["model"]["l_ref"]
                            for d in deps])
        self.n = np.array([d["n_replicas"] for d in deps], np.float64)
        self.rtt = np.array([d["instance"]["net_rtt"] for d in deps])
        router = conf["router"]
        self.tau = router["x"] * base + (self.rtt if router["slo_includes_rtt"]
                                         else 0.0)
        tab = conf["erlang_table"]
        grid = np.linspace(0.0, 1.0, tab["points"])
        self.table = np.array(
            [[min(erlang_c_wait(float(r * n * mu), int(n), float(mu)),
                  tab["cap_s"]) for r in grid]
             for n, mu in zip(self.n, self.mu)])
        # home: the edge deployment of each model; up: its cloud one
        self.home, self.up = {}, {}
        for i, d in enumerate(deps):
            m = d["model"]["name"]
            if d["instance"]["tier"] == "edge" and m not in self.home:
                self.home[m] = i
        for m, h in self.home.items():
            clouds = [i for i, d in enumerate(deps)
                      if d["model"]["name"] == m
                      and d["instance"]["tier"] == "cloud"]
            self.up[m] = clouds[0] if clouds else -1
        self.columns = {"alpha": self.alpha, "beta": self.beta,
                        "gamma": self.gamma, "mu": self.mu, "n": self.n,
                        "rtt": self.rtt}

    def scores(self, lam: np.ndarray):
        """(g, rho) over an (R, I) matrix of per-candidate rates."""
        q = self.q
        lam = q(lam)
        lam_t = q(lam / q(np.maximum(self.n, 1.0)))
        proc = q(q(self.alpha) + q(q(self.beta) * q(
            np.power(np.maximum(lam_t, 0.0), q(self.gamma)))))
        rho = q(lam / q(np.maximum(q(self.n * self.mu), 1e-12)))
        t = self.table.shape[1]
        pos = q(np.clip(rho, 0.0, 1.0) * (t - 1))
        lo = np.clip(np.floor(pos).astype(np.int64), 0, t - 2)
        frac = q(pos - lo)
        cols = np.arange(self.table.shape[0])[None, :]
        tab = q(self.table)
        wait = q(q(tab[cols, lo] * q(1.0 - frac)) + q(tab[cols, lo + 1] * frac))
        g = q(q(proc + q(self.rtt)) + wait)
        return g, rho

    def guard(self, lam: np.ndarray, model: str):
        """Algorithm-1 guard for R requests of ``model``. Returns
        (chosen (R,), g at chosen (R,), offloaded (R,), g_inst (R,),
        rho at home (R,), rho at chosen (R,))."""
        q = self.q
        g, rho = self.scores(lam)
        g_eff = np.where(rho < 1.0, g, UNSTABLE)
        h, u = self.home[model], self.up[model]
        rows = np.arange(lam.shape[0])
        g_home = g_eff[:, h]
        g_inst = np.where(g_home < UNSTABLE, q(g_home - q(self.rtt[h])),
                          g_home)
        off = (g_inst > q(self.tau[h])) & (u >= 0)
        chosen = np.where(off, u, h)
        return (chosen, g_eff[rows, chosen], off, g_inst, rho[:, h],
                rho[rows, chosen])

    def window_rates(self, flushes: list, width: float) -> list:
        """The (R, I) rate matrix of each window of a run, in order.

        ``flushes`` holds, for each window in the order they closed, its
        closing time and, in decision order, each request's (home
        column, column it went to, or -1). Algorithm 1's rate of a
        deployment at time t is the number of arrivals it saw in the
        last ``width`` seconds (t - t_arrival <= width) over ``width``;
        a request arrives at its home when its window closes, and also
        at the deployment it is sent to, when that is another. Row r of
        a window adds the r + 1 requests decided up to it, spread over
        ``width``: lam[r, i] = rate_i(t) + (r + 1) / width."""
        n = len(self.keys)
        seen: list = [[] for _ in range(n)]
        first = [0] * n
        out = []
        for t, rows in flushes:
            rate = np.empty(n)
            for i, times in enumerate(seen):
                while first[i] < len(times) and t - times[first[i]] > width:
                    first[i] += 1
                rate[i] = (len(times) - first[i]) / width
            own = np.arange(1, len(rows) + 1, dtype=np.float64) / width
            out.append(rate[None, :] + own[:, None])
            for home, went in rows:
                for i in {home, went} - {-1}:
                    seen[i].append(t)
        return out

    def score_band(self, lam: np.ndarray, cols: np.ndarray,
                   rel: float = RATE_ULPS):
        """(low, high) of g at column ``cols[r]`` of each row over rates
        within ``rel`` of ``lam``: g rises with the rate, and a float32
        computation of rho = lam / (n mu) and of its grid position is
        off by a few ulps, which the steep last segment of the
        Erlang-C table magnifies."""
        rows = np.arange(lam.shape[0])
        lo, _ = self.scores(lam * (1.0 - rel))
        hi, _ = self.scores(lam * (1.0 + rel))
        return lo[rows, cols], hi[rows, cols]
