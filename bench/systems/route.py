"""Route cells: an open-loop stream of requests into one ``ControlPlane``
on the Pallas path, single-threaded.

Set-up builds the cluster of the configuration, draws every due time
from the seed, makes every request, compiles each row bucket of the
routing kernel that the cell's ``max_batch`` can reach, warms the plane
of the window with one request decided ten seconds before the window
opens (it has left the 1 s sliding rates by then), and moves what it
made out of the garbage collector's way (``gc.freeze``). In the window
each request is submitted through ``ControlPlane.submit`` when it is
due; a window whose age has reached ``AdmissionConfig.window`` is
flushed through ``flush`` even when no arrival comes. Arrivals stop at
``--seconds``; the loop then runs until the last open window has been
flushed. A request without a decision by then counts in ``failed``.

Of every window the harness keeps the time it closed, the rate matrix
the policy built, the kernel's outputs and the decisions. After the
window ``bench/reference/routing.py`` recomputes the rates from the
closing times and the decisions and decides every request again at
float64 (see ``check``).
"""
from __future__ import annotations

import gc
import math
import time
from typing import Callable, Optional

import numpy as np

from bench import traffic
from bench.reference.routing import UNSTABLE, Reference

# disagreements inside these bands are ties, judged from the
# reference's own float64 scores: f32 scores differ by a few ulps
TIE_REL, TIE_ABS = 2e-5, 1e-6
KERNEL = "routing_guard"


def build_cluster(conf: dict):
    from repro.core.catalogue import Cluster, Deployment
    from repro.core.latency_model import InstanceClass, ModelProfile
    from repro.core.scheduler import QualityClass
    return Cluster([
        Deployment(ModelProfile(**d["model"]), InstanceClass(**d["instance"]),
                   QualityClass[d["quality"]], n_replicas=d["n_replicas"],
                   n_max=d["n_max"], gamma=d["gamma"],
                   startup_delay=d["startup_delay"])
        for d in conf["deployments"]])


def make_plane(conf: dict, admission: dict, max_batch: Optional[int] = None):
    from repro.control import ControlPlane
    from repro.control.admission import AdmissionConfig
    from repro.core.router import RouterParams
    r = conf["router"]
    cfg = AdmissionConfig(
        backend=conf["backend"], policy=conf["policy"],
        window=float(admission["window"]),
        max_batch=int(max_batch or admission["max_batch"]),
        block_r=int(admission["block_r"]),
        erlang_table_size=int(conf["erlang_table"]["points"]))
    params = RouterParams(x=r["x"], window=r["window"],
                          slo_includes_rtt=r["slo_includes_rtt"])
    return ControlPlane(build_cluster(conf), params=params, config=cfg,
                        policy=conf["policy"])


class Recorder:
    """Keeps, of every launch of the routing kernel, its outputs as host
    arrays, and of every window the rate matrix the policy built for it
    (``lam_matrix``, a host array). The candidate columns are kept once
    for each distinct set the launches pass. Nothing else of a launch
    stays alive. ``replace`` puts another function in the kernel's place
    (the control and the fault tests use it)."""

    def __init__(self, policy, replace: Optional[Callable] = None):
        from repro.kernels import ops
        self.ops, self.policy = ops, policy
        self.orig = getattr(ops, KERNEL)
        inner = replace or self.orig
        self.outs: list = []
        self.lams: list = []
        self.columns: dict = {}
        build = policy.lam_matrix

        def wrapped(*args, **kw):
            out = inner(*args, **kw)
            # the policy reads these back at once; a second read is free
            self.outs.append(tuple(np.asarray(o) for o in out))
            key = tuple(id(a) for a in args[1:7])
            if key not in self.columns:
                self.columns[key] = args[1:7]
            return out

        def lam_matrix(reqs, t_now):
            lam = build(reqs, t_now)
            self.lams.append(lam)
            return lam
        setattr(ops, KERNEL, wrapped)
        policy.lam_matrix = lam_matrix

    def clear(self) -> None:
        self.outs.clear()
        self.lams.clear()

    def restore(self) -> None:
        setattr(self.ops, KERNEL, self.orig)
        del self.policy.lam_matrix


def buckets(max_batch: int, block_r: int) -> list[int]:
    """One window size per distinct (block, padded rows) launch shape a
    window of 1..max_batch requests can take (``_pad_block``)."""
    seen, sizes = set(), []
    for r in range(1, max_batch + 1):
        p2 = 1 << max(3, (r - 1).bit_length())
        block = min(block_r, p2)
        key = (block, -(-r // block) * block)
        if key not in seen:
            seen.add(key)
            sizes.append(r)
    return sizes


def _requests(mix: dict, due: np.ndarray):
    from repro.core.scheduler import QualityClass, Request
    q = QualityClass[mix["quality"]]
    return [Request(model=mix["model"], quality=q, arrival=float(t))
            for t in due]


def warm(conf: dict, admission: dict, mix: dict, plane) -> list:
    """Compile every launch shape the window can use, on a plane of its
    own, then decide one request on the window's plane 10 s before the
    window opens. Returns that window as ``drive`` records one, and its
    request."""
    for size in buckets(int(admission["max_batch"]),
                        int(admission["block_r"])):
        wp = make_plane(conf, admission, max_batch=size)
        for rq in _requests(mix, np.full(size, -100.0)):
            wp.submit(rq, -100.0)
        if wp.pending():
            wp.flush(-100.0)
    decs, reqs = [], _requests(mix, np.array([-10.0]))
    for rq in reqs:
        decs += plane.submit(rq, -10.0) or []
    if plane.pending():
        decs += plane.flush(-10.0)
    return ([(-10.0, [(d.req.req_id, d.target_key, d.outcome)
                      for d in decs])], reqs)


def drive(plane, reqs: list, due: np.ndarray, seconds: float, spans,
          clock0: float) -> dict:
    """The open loop. Returns per-request decision times (NaN where
    none), submit lags, and of each flush in order the time it was
    given and its decisions as (req_id, target, outcome); the decision
    objects themselves are let go, so that the benchmark's bookkeeping
    adds no long-lived objects to Python's garbage collector."""
    n = len(reqs)
    window = plane.cfg.window
    index = {rq.req_id: j for j, rq in enumerate(reqs)}
    decided = np.full(n, np.nan)
    lags = np.zeros(n)
    flushes = []
    perf = time.perf_counter
    span_of = spans.spans
    i = 0
    deadline = seconds + window + 1.0

    def settle(decs, t_given, t_a, t_b):
        span_of["flush"].append((t_a, t_b))
        t = t_b - clock0
        kept = []
        for d in decs:
            decided[index[d.req.req_id]] = t
            kept.append((d.req.req_id, d.target_key, d.outcome))
        flushes.append((t_given, kept))

    while True:
        now = perf() - clock0
        while i < n and due[i] <= now:
            lags[i] = now - due[i]
            with spans.span("submit"):
                t_a = perf()
                decs = plane.submit(reqs[i], now)
                t_b = perf()
            i += 1
            if decs is not None:
                settle(decs, now, t_a, t_b)
            now = perf() - clock0
        opened = plane.window_opened_at()
        if opened is not None and now - opened >= window:
            with spans.span("submit"):
                t_a = perf()
                decs = plane.flush(now)
                t_b = perf()
            settle(decs, now, t_a, t_b)
            continue
        if (i >= n and opened is None) or now > deadline:
            break
        nxt = min(due[i] if i < n else math.inf,
                  opened + window if opened is not None else math.inf)
        dt = nxt - (perf() - clock0)
        if dt > 5e-4:
            with spans.span("wait"):
                time.sleep(dt - 3e-4)
    return {"decided": decided, "lags": lags, "flushes": flushes,
            "submitted": i}


def check(conf: dict, recorder: Recorder, before: tuple, flushes: list,
          reqs: list, plane, ledger0: dict) -> tuple[dict, dict]:
    """Replay the window through the float64 reference.

    The reference recomputes each window's rates from the times the
    windows closed and the decisions taken in them (``before``: the
    windows decided before the measured one, and their requests),
    decides every request from those rates and compares:

    * ``decision_mismatches``: requests whose offload choice differs from
      the reference outside the tie bands.
    * ``score_rel_gap``: the largest distance, relative to the
      reference's estimate, from the kernel's latency estimate at the
      chosen deployment to the band the reference gives for rates
      within a few float32 ulps of the request's (``score_band``), over
      requests both place alike.
    * ``plane_errors``: rows of a rate matrix the policy built that
      differ from the reference's, candidate columns that differ from
      the configuration, windows without exactly one launch, requests
      out of their lane's first-come order, decisions whose binding
      (target and outcome) is not what the kernel chose or whose choice
      is neither home nor one hop up, requests decided twice or never,
      and a broken conservation ledger.
    """
    from repro.control.admission import ADMITTED, OFFLOADED
    ref = Reference(conf)
    keys = [d.key for d in plane.policy.deps]
    col = {k: i for i, k in enumerate(ref.keys)}
    errors = 0
    if keys != ref.keys:
        errors += 1
    if not (len(recorder.outs) == len(recorder.lams) == len(flushes)):
        errors += 1
    for cols in recorder.columns.values():
        for name, got in zip(("alpha", "beta", "gamma", "mu", "n", "rtt"),
                             cols):
            if not np.allclose(np.asarray(got), ref.columns[name],
                               rtol=1e-6, atol=0.0):
                errors += 1
    earlier, earlier_reqs = before
    meta = {rq.req_id: (j, rq) for j, rq in enumerate(reqs)}
    model_of = {rq.req_id: rq.model for rq in earlier_reqs + reqs}
    history = [(t, [(ref.home.get(model_of[rid], -1), col.get(target, -1))
                    for rid, target, _ in decs])
               for t, decs in earlier + flushes]
    lams = ref.window_rates(history,
                            conf["router"]["window"])[len(earlier):]
    mismatches, max_gap = 0, 0.0
    seen = {}
    for lam, prog_lam, out, (_, decs) in zip(lams, recorder.lams,
                                             recorder.outs, flushes):
        n_rows = len(decs)
        if prog_lam.shape != lam.shape:
            errors += 1
            continue
        errors += int(np.sum(~np.all(np.isclose(
            np.asarray(prog_lam, np.float64), lam, rtol=1e-6, atol=0.0),
            axis=1)))
        order = [(int(meta[rid][1].quality), meta[rid][0])
                 for rid, _, _ in decs]
        errors += order != sorted(order)
        model = meta[decs[0][0]][1].model
        h, u = ref.home[model], ref.up[model]
        idx, g_sel, off = (o[:n_rows] for o in out)
        chosen, g_ref, off_ref, g_inst, rho_home, rho_ch = ref.guard(lam,
                                                                     model)
        g_lo, g_hi = ref.score_band(lam, chosen)
        band_tau = TIE_REL * abs(ref.tau[h]) + TIE_ABS
        for r, (rid, target, outcome) in enumerate(decs):
            seen[rid] = seen.get(rid, 0) + 1
            want_out = OFFLOADED if bool(off[r]) else ADMITTED
            if target != keys[int(idx[r])] or outcome != want_out:
                errors += 1
            if int(idx[r]) != (u if off[r] else h):
                errors += 1
            if meta[rid][1].model != model:
                errors += 1
            if bool(off[r]) != bool(off_ref[r]):
                tied = (abs(g_inst[r] - ref.tau[h]) <= band_tau
                        or abs(rho_home[r] - 1.0) <= 1e-6)
                mismatches += not tied
                continue
            if abs(rho_ch[r] - 1.0) <= 1e-6:
                continue
            if g_ref[r] >= UNSTABLE:
                mismatches += float(g_sel[r]) != UNSTABLE
                continue
            g = float(g_sel[r])
            max_gap = max(max_gap, max(g_lo[r] - g, g - g_hi[r], 0.0)
                          / abs(g_ref[r]))
    n = len(reqs)
    errors += sum(1 for rq in reqs if seen.get(rq.req_id, 0) != 1)
    try:
        plane.check_conservation()
    except AssertionError:
        errors += 1
    got = {k: plane.outcomes[k] - ledger0[k] for k in plane.outcomes}
    if plane.decided - ledger0["decided"] != n \
            or got[ADMITTED] + got[OFFLOADED] != n:
        errors += 1
    return ({"decision_mismatches": mismatches, "score_rel_gap": max_gap,
             "plane_errors": errors},
            {"launches": len(recorder.outs)})


def run(run, replace: Optional[Callable] = None, mix: Optional[dict] = None):
    """One run of a route cell (``bench.harness.Run``). ``replace`` and
    ``mix`` are for the control, the fault tests and the knee sweep."""
    from bench.harness import Outcome
    cell = run.cell
    conf, admission = cell.config, cell.params["admission"]
    mix = mix or cell.traffic
    limits = cell.params["limits"]
    due = traffic.arrivals(mix, run.seed, run.seconds)
    plane = make_plane(conf, admission)
    recorder = Recorder(plane.policy, replace)
    try:
        before = warm(conf, admission, mix, plane)
        reqs = _requests(mix, due)
        recorder.clear()
        ledger0 = dict(plane.outcomes, decided=plane.decided)
        gc.collect()
        gc.freeze()
        run.begin_window()
        res = drive(plane, reqs, due, run.seconds, run.spans,
                    run.window_t0)
        run.end_window()
        gc.unfreeze()
    finally:
        recorder.restore()
    decided = res["decided"]
    n_req = len(reqs)
    lat = (decided - due)[np.isfinite(decided)] * 1e3
    failed = int(np.sum(~np.isfinite(decided)))
    numbers, extra = check(conf, recorder, before, res["flushes"], reqs,
                           plane, ledger0)
    checks = {k: (v, limits[k]) for k, v in numbers.items()}
    checks["undecided"] = (failed, 0)
    run.spans.samples["submit_lag_ms"] = list(res["lags"] * 1e3)
    run.spans.samples["decision_ms"] = list(lat)
    extra.update(flushes=len(res["flushes"]), decided=int(lat.size),
                 kernel=KERNEL)
    metrics = {}
    if lat.size:
        metrics = {f"decision_p{q}_ms": float(np.percentile(lat, q))
                   for q in (50, 95, 99)}
    longest = {k: max((t1 - t0 for t0, t1 in run.spans.spans.get(k, ())),
                      default=0.0) * 1e3 for k in ("flush", "submit", "wait")}
    print(f"route: {len(reqs)} requests, {len(res['flushes'])} flushes, "
          f"{extra['launches']} launches, p50 "
          f"{metrics.get('decision_p50_ms')} ms, p95 "
          f"{metrics.get('decision_p95_ms')} ms, p99 "
          f"{metrics.get('decision_p99_ms')} ms, submit lag max "
          f"{float(np.max(res['lags'], initial=0.0)) * 1e3:.3f} ms at "
          f"{float(due[int(np.argmax(res['lags']))]) if n_req else 0.0:.3f} s;"
          f" longest flush {longest['flush']:.3f} ms, submit "
          f"{longest['submit']:.3f} ms, wait {longest['wait']:.3f} ms")
    return Outcome(attempted=len(reqs), failed=failed, metrics=metrics,
                   checks=checks, extra=extra)
