"""Chip smoke test: the LA-IMR control plane, the JAX fleet simulator and
a full-width StableLM-3B replica, run once on one TPU through the entry
points a user calls.

  python chip_smoke.py [--seed 0]          # phases a-c on one chip
  python chip_smoke.py --four-chip         # only the sharded decode (4 chips)

One process runs every phase, in order:

a. Control plane. ``paper_cluster`` behind ``ControlPlane`` with
   ``AdmissionConfig(backend="pallas")`` for each registered policy, at
   windows of 1, 64 and 256 requests and one 256-request window of four
   64-row blocks. Every window decision must go through the compiled
   Pallas kernel (``tpu_custom_call`` in its HLO) and agree with the same
   kernel's ``impl="ref"`` oracle run on the chip, except where both
   scores lie inside the pinned tie bands; the conservation ledger must
   balance.
b. Served path. ``get_config("stablelm_3b")`` at full width in bf16,
   weights drawn on the device from ``--seed``, one
   ``ServingEngine(slots=8, max_len=1024)`` as the edge engine of a
   ``guarded_alg1`` plane: 8 requests are admitted, their 128-token
   prompts prefilled, 32 tokens decoded, every slot released. The
   Pallas path's prefill logits and first decode steps' logits must
   match the ``impl="ref"`` path within ``LOGIT_REL_TOL``.
c. Simulator. ``SimConfig(backend="jax")`` on a 200k-arrival flash
   trace: exactly one latency sample per arrival, and P50/P99/offload
   within ``jaxsim.TOLERANCES`` of the event loop on the same trace.

``--four-chip`` runs only the StableLM-3B decode step on a (data=2,
model=2) mesh placed by ``distributed.sharding`` and compares its logits
with the same step on one chip.

Everything worth reading is printed on earlier lines; the last line is
one JSON object ``{"ok": true, "device": {...}}``. The script exits
non-zero, without that line, when JAX finds no TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

POLICIES = ("route_best", "guarded_alg1", "safetail", "reliable", "hybrid")
# (requests per window, AdmissionConfig.block_r): single-block windows of
# the pow2 buckets 8 (from 1), 64 and 256, then four blocks of 64 rows
WINDOWS = ((1, 256), (64, 256), (256, 256), (256, 64))
# decision tie bands: the route_best near band is 1e-5 relative; kernel
# and oracle scores differ by a few f32 ulps on top of it
TIE_REL, TIE_ABS = 2e-5, 1e-6
# served-path logits: bf16 weights and activations through 32 layers;
# kernel and oracle attention round differently, so agreement is a
# relative L2 distance over each logits row block, not bit equality
LOGIT_REL_TOL = 5e-2
PROMPT_LEN, DECODE_STEPS, COMPARED_STEPS = 128, 32, 4
SIM_ARRIVALS, SIM_LAM = 200_000, 2000.0


class SmokeFailure(RuntimeError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ #
# compile accounting
# ------------------------------------------------------------------ #
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = collections.Counter()


def _on_event(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s["total"] += duration


def compile_seconds() -> float:
    return _compile_s["total"]


# ------------------------------------------------------------------ #
# a. control plane
# ------------------------------------------------------------------ #
class KernelRecorder:
    """Wraps the routing ops so every kernel launch a plane flush makes
    is kept (arguments and outputs) for replay against the oracle."""

    NAMES = ("routing_score", "routing_guard", "routing_topk",
             "routing_attain")

    def __init__(self):
        from repro.kernels import ops
        self.ops = ops
        self.calls = []
        self.orig = {n: getattr(ops, n) for n in self.NAMES}
        for n, fn in self.orig.items():
            setattr(ops, n, self._wrap(n, fn))

    def _wrap(self, name, fn):
        @functools.wraps(fn)
        def rec(*args, **kw):
            out = fn(*args, **kw)
            self.calls.append((name, args, kw,
                               tuple(np.asarray(o) for o in out)))
            return out
        return rec

    def restore(self):
        for n, fn in self.orig.items():
            setattr(self.ops, n, fn)


def _kernel_fn(name):
    from repro.kernels import routing_decide as rd
    from repro.kernels import routing_score as rs
    return {"routing_score": rs.routing_score,
            "routing_guard": rd.routing_guard,
            "routing_topk": rd.routing_topk,
            "routing_attain": rd.routing_attain}[name]


def _requests(n, rng, t0):
    from repro.core.scheduler import QualityClass, Request
    lanes = (("efficientdet", QualityClass.LOW_LATENCY),
             ("yolov5m", QualityClass.BALANCED),
             ("faster_rcnn", QualityClass.PRECISE))
    pick = rng.integers(0, len(lanes), n)
    return [Request(model=lanes[k][0], quality=lanes[k][1],
                    arrival=t0 + 1e-3 * j) for j, k in enumerate(pick)]


def _band(x):
    return TIE_REL * np.abs(x) + TIE_ABS


class TieJudge:
    """Decides whether a kernel/oracle disagreement lies inside the
    pinned tie bands, from the oracle's own (R, I) scores."""

    def __init__(self, name, args, kw):
        from repro.kernels import ref
        lam, alpha, beta, gamma, mu, n, rtt = args[:7]
        table = args[-1]
        g, rho = ref._table_scores(lam, alpha, beta, gamma, mu, n, rtt,
                                   table)
        self.g, self.rho = np.asarray(g), np.asarray(rho)
        self.rtt = np.asarray(rtt)
        self.name, self.args = name, args
        r, i = self.g.shape
        margin = float(kw.get("margin", 0.0))
        if name == "routing_guard":
            self.slo = np.full((r, i), np.inf, np.float32)
        else:
            slo = np.asarray(args[7], np.float32)
            self.slo = np.broadcast_to(slo if slo.ndim == 2 else slo[None],
                                       (r, i))
        self.gate = self.slo - np.float32(margin)
        self.p = None
        if name == "routing_attain":
            sigma = np.asarray(args[8])[None, :]
            avail = np.asarray(args[9])[None, :]
            z = (np.log(np.maximum(self.slo, 1e-20))
                 - np.log(np.maximum(self.g, 1e-20))) / (
                np.maximum(sigma, 1e-20) * np.sqrt(2.0))
            phi = 0.5 * (1.0 + np.asarray(ref.erf(jnp.asarray(
                np.clip(z, -10.0, 10.0), jnp.float32))))
            self.p = avail * np.where(sigma > 0.0, phi, self.g <= self.slo)

    def on_edge(self, r, c):
        g = self.g[r, c]
        return bool(abs(g - self.slo[r, c]) <= _band(g)
                    or abs(g - self.gate[r, c]) <= _band(g)
                    or abs(self.rho[r, c] - 1.0) <= 1e-6)

    def tied(self, r, a, b):
        if a == b:
            return True
        if a < 0 or b < 0:
            return self.on_edge(r, max(a, b))
        ga, gb = self.g[r, a], self.g[r, b]
        if abs(ga - gb) <= _band(max(abs(ga), abs(gb))):
            return True
        if self.p is not None and abs(self.p[r, a] - self.p[r, b]) <= 2e-6:
            return True
        return self.on_edge(r, a) or self.on_edge(r, b)

    def ok_tied(self, r):
        return any(self.on_edge(r, c) for c in range(self.g.shape[1]))

    def guard_tied(self, r):
        home = int(np.asarray(self.args[8])[r])
        tau = float(np.asarray(self.args[7])[r])
        g_home = self.g[r, home] if self.rho[r, home] < 1.0 else 1e9
        g_inst = g_home - self.rtt[home] if g_home < 1e9 else g_home
        return abs(g_inst - tau) <= _band(tau) or \
            abs(self.rho[r, home] - 1.0) <= 1e-6


def compare_call(name, args, kw, got, want, rows):
    """(mismatches outside the tie bands, differences inside them, the
    largest relative score difference where both chose alike)."""
    judge = TieJudge(name, args, kw)
    bad, ties, max_rel = [], 0, 0.0
    idx_g, g_g, flag_g = got
    idx_w, g_w, flag_w = want
    idx_g = idx_g.reshape(idx_g.shape[0], -1)
    idx_w = idx_w.reshape(idx_w.shape[0], -1)
    g_g = g_g.reshape(idx_g.shape)
    g_w = g_w.reshape(idx_w.shape)
    for r in range(rows):
        if flag_g[r] != flag_w[r]:
            tied = judge.guard_tied(r) if name == "routing_guard" \
                else judge.ok_tied(r)
            what = "offload" if name == "routing_guard" else "ok"
            ties += tied
            if not tied:
                bad.append(f"row {r}: {what} {flag_g[r]} vs {flag_w[r]}")
        for c in range(idx_g.shape[1]):
            a, b = int(idx_g[r, c]), int(idx_w[r, c])
            if a == b:
                if a >= 0 and np.isfinite(g_w[r, c]) and g_w[r, c] != 0:
                    max_rel = max(max_rel, abs(g_g[r, c] - g_w[r, c])
                                  / abs(g_w[r, c]))
            elif judge.tied(r, a, b):
                ties += 1
            else:
                bad.append(f"row {r} col {c}: candidate {a} vs {b} "
                           f"(g {judge.g[r, a] if a >= 0 else None} vs "
                           f"{judge.g[r, b] if b >= 0 else None})")
    return bad, ties, max_rel


def phase_control_plane(seed: int) -> dict:
    from repro.control import ControlPlane
    from repro.control.admission import AdmissionConfig
    from repro.core.catalogue import paper_cluster

    rng = np.random.default_rng(seed)
    recorder = KernelRecorder()
    checked_hlo = set()
    stats = {"windows": 0, "decisions": 0, "kernel_calls": 0,
             "mismatches": 0, "ties": 0, "max_rel_g": 0.0}
    try:
        for policy in POLICIES:
            for n_req, block_r in WINDOWS:
                cfg = AdmissionConfig(backend="pallas", policy=policy,
                                      window=1e9, max_batch=n_req,
                                      block_r=block_r)
                plane = ControlPlane(paper_cluster(), config=cfg,
                                     policy=policy)
                require(plane.policy._impl() == "pallas",
                        f"{policy}: policy resolves to "
                        f"{plane.policy._impl()!r}, not the Pallas kernel")
                reqs = _requests(n_req, rng, t0=10.0)
                recorder.calls.clear()
                t0 = time.perf_counter()
                decisions = []
                for rq in reqs:
                    decisions.extend(plane.submit(rq, rq.arrival) or [])
                dt = time.perf_counter() - t0
                plane.check_conservation()
                require(plane.decided == n_req,
                        f"{policy}: {plane.decided} of {n_req} decided")
                require(len(recorder.calls) == 1,
                        f"{policy}: {len(recorder.calls)} kernel launches "
                        "for one window")
                name, args, kw, got = recorder.calls[0]
                require(kw.get("impl") == "pallas",
                        f"{policy}: launched impl={kw.get('impl')!r}")
                key = (name, tuple(a.shape for a in args),
                       tuple(sorted((k, v) for k, v in kw.items()
                                    if k != "impl")))
                if key not in checked_hlo:
                    static = {k: v for k, v in kw.items() if k != "impl"}
                    hlo = _kernel_fn(name).lower(*args, **static) \
                        .compile().as_text()
                    require("tpu_custom_call" in hlo,
                            f"{name}: no tpu_custom_call in compiled HLO")
                    checked_hlo.add(key)
                want = tuple(np.asarray(o) for o in recorder.orig[name](
                    *args, **{**kw, "impl": "ref"}))
                bad, ties, max_rel = compare_call(name, args, kw, got,
                                                  want, rows=n_req)
                for m in bad:
                    print(f"  MISMATCH {policy} R={n_req} "
                          f"block_r={block_r}: {m}")
                stats["windows"] += 1
                stats["decisions"] += len(decisions)
                stats["kernel_calls"] += 1
                stats["mismatches"] += len(bad)
                stats["ties"] += ties
                stats["max_rel_g"] = max(stats["max_rel_g"], max_rel)
                outcomes = {k: v for k, v in plane.outcomes.items() if v}
                # flush_s: the window's first flush, compile included
                print(f"  {policy:12s} R={n_req:3d} block_r={block_r:3d} "
                      f"kernel={name} flush_s={dt:.6f} "
                      f"max_rel_g={max_rel:.3e} outcomes={outcomes}")
    finally:
        recorder.restore()
    require(stats["mismatches"] == 0,
            f"{stats['mismatches']} kernel/oracle mismatches outside the "
            "tie bands")
    return stats


# ------------------------------------------------------------------ #
# b. served path
# ------------------------------------------------------------------ #
def _params(cfg, seed, shardings=None):
    from repro.models import model
    init = jax.jit(lambda k: model.init_params(k, cfg),
                   out_shardings=shardings)
    params = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def _rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _logits_trace(cfg, params, prompts, impl, feed=None):
    """Prefill + COMPARED_STEPS decode steps through ``impl``; returns
    (list of logits, tokens fed, fastest of 3 warm decode steps in ms).
    ``feed`` pins the decode inputs, so two paths see identical
    tokens."""
    from repro.kernels import ops
    from repro.models import model
    ops.set_implementation(impl)
    prefill = jax.jit(lambda p, b: model.prefill(p, cfg, b))
    decode = jax.jit(lambda p, t, c, q: model.decode_step(p, cfg, t, c, q))
    logits, cache = prefill(params, {"tokens": prompts})
    out = [np.asarray(logits, np.float32)]
    fed = []
    pos = jnp.full((prompts.shape[0],), prompts.shape[1], jnp.int32)
    for step in range(COMPARED_STEPS):
        tok = feed[step] if feed is not None else \
            jnp.argmax(logits, -1).astype(jnp.int32)
        fed.append(tok)
        logits, cache = decode(params, tok, cache, pos)
        out.append(np.asarray(logits, np.float32))
        pos = pos + 1
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(decode(params, tok, cache, pos))
        warm.append(time.perf_counter() - t0)
    return out, fed, min(warm) * 1e3


def phase_served(seed: int) -> dict:
    from benchmarks.common import experiment_cluster
    from repro.configs.base import get_config
    from repro.control.admission import SlotBank
    from repro.core.scheduler import QualityClass, Request
    from repro.kernels import ops
    from repro.serving import AdmissionConfig, BatchRouter
    from repro.serving.engine import ServingEngine

    cfg = get_config("stablelm_3b")
    require(ops.get_implementation() == "pallas",
            f"kernels resolve to {ops.get_implementation()!r} on the TPU")
    c0, t0 = compile_seconds(), time.perf_counter()
    params = _params(cfg, seed)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"  weights: {n_params / 1e9:.3f}B params "
          f"({sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.2f} GB "
          f"{cfg.dtype}) drawn in {time.perf_counter() - t0:.1f}s "
          f"(compile {compile_seconds() - c0:.1f}s)")

    engine = ServingEngine(cfg, params, slots=8, max_len=1024)
    edge, cloud = "yolov5m@pi4-edge", "yolov5m@cloud"
    plane = BatchRouter(
        experiment_cluster(), engines={edge: engine, cloud: SlotBank(16)},
        config=AdmissionConfig(window=1e9, max_batch=8,
                               policy="guarded_alg1", backend="pallas"))
    require(plane.policy._impl() == "pallas", "plane is not on the kernel")
    decisions = []
    for j in range(8):
        got = plane.submit(Request(model="yolov5m",
                                   quality=QualityClass.BALANCED,
                                   arrival=1e-3 * j), 1e-3 * j)
        decisions.extend(got or [])
    plane.check_conservation()
    served = [d for d in decisions if d.target_key == edge]
    slots = sorted(d.slot for d in served)
    print(f"  plane: {dict(collections.Counter(d.outcome for d in decisions))}"
          f", edge slots {slots}")
    require(served, "the guard admitted nothing to the served replica")
    require(slots == list(range(len(slots))),
            f"admitted slots {slots} are not the engine's leading slots")

    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (len(slots), PROMPT_LEN), 0, cfg.vocab_size,
                                 jnp.int32)
    c0, t0 = compile_seconds(), time.perf_counter()
    out = engine.generate(prompts, steps=DECODE_STEPS)
    gen_s = time.perf_counter() - t0
    toks = np.asarray(out.tokens)
    require(toks.shape == (len(slots), DECODE_STEPS),
            f"tokens shape {toks.shape}")
    require(((toks >= 0) & (toks < cfg.vocab_size)).all(),
            "tokens out of vocabulary range")
    print(f"  generate: prefill {len(slots)}x{PROMPT_LEN} + {DECODE_STEPS} "
          f"tokens in {gen_s:.2f}s (compile {compile_seconds() - c0:.1f}s)")
    print(f"  tokens (not gated, random weights): {toks[0, :8].tolist()} ...")
    steps = []
    for _ in range(4):      # warm steps on the occupied engine
        t0 = time.perf_counter()
        engine.step()
        steps.append(time.perf_counter() - t0)
    print(f"  warm decode step (host clock, incl. readback): "
          + ", ".join(f"{x * 1e3:.2f}ms" for x in steps))
    for d in served:
        plane.first_completion(d.req.req_id)
        engine.release(d.slot)
    require(engine.n_free() == engine.slots, "slots leaked after release")
    plane.check_conservation()
    del engine, plane

    c0, t0 = compile_seconds(), time.perf_counter()
    got, fed, ms_pallas = _logits_trace(cfg, params, prompts, "pallas")
    want, _, ms_ref = _logits_trace(cfg, params, prompts, "ref", feed=fed)
    ops.set_implementation("pallas")
    rels = [_rel_l2(g, w) for g, w in zip(got, want)]
    print(f"  logits pallas vs ref: rel L2 prefill {rels[0]:.3e}, decode "
          f"{', '.join(f'{r:.3e}' for r in rels[1:])} (tol "
          f"{LOGIT_REL_TOL}); {time.perf_counter() - t0:.1f}s "
          f"(compile {compile_seconds() - c0:.1f}s)")
    print(f"  warm decode step, {PROMPT_LEN}-slot cache: pallas "
          f"{ms_pallas:.2f}ms, ref {ms_ref:.2f}ms (host clock)")
    for g in got:
        require(np.isfinite(g).all(), "non-finite logits")
        require(g.shape == (len(slots), cfg.vocab_size),
                f"logits shape {g.shape}")
    require(max(rels) <= LOGIT_REL_TOL,
            f"Pallas logits disagree with the oracle: {rels}")
    return {"served": len(slots), "max_rel_logits": max(rels),
            "decode_step_ms": min(steps) * 1e3}


# ------------------------------------------------------------------ #
# c. simulator
# ------------------------------------------------------------------ #
def phase_simulator(seed: int) -> dict:
    from benchmarks.bench_sim_throughput import (check_equivalence,
                                                 fleet_cluster, make_trace,
                                                 run_once)
    from repro.core.simulator import ClusterSimulator, SimConfig

    arr = make_trace("flash", SIM_ARRIVALS, SIM_LAM, seed)
    n = len(arr)
    c0, t0 = compile_seconds(), time.perf_counter()
    res = ClusterSimulator(fleet_cluster(), SimConfig(
        mode="laimr", seed=seed, backend="jax")).run(arr)
    cold_s = time.perf_counter() - t0
    lat = np.asarray(res.latency_trace)
    require(res.n_arrivals == n and lat.size == n,
            f"{lat.size} latency samples for {n} arrivals")
    require(np.isfinite(lat).all() and (lat > 0).all(),
            "non-finite or non-positive latency samples")
    print(f"  jax twin cold run {cold_s:.1f}s "
          f"(compile {compile_seconds() - c0:.1f}s), {n} arrivals, "
          f"{lat.size} samples")
    twin = run_once(fleet_cluster, "laimr", "jax", arr, seed, warmup=0)
    oracle = run_once(fleet_cluster, "laimr", "event", arr, seed, warmup=0)
    for row in (twin, oracle):
        print(f"  {row['backend']:5s} wall {row['wall_s']:.3f}s "
              f"arrivals/s {row['arrivals_per_s']:.0f} p50 "
              f"{row['p50_s']:.4f}s p99 {row['p99_s']:.4f}s offload "
              f"{row['offload_rate']:.4f}")
    errs = check_equivalence(oracle, twin)
    for e in errs:
        print(f"  EQUIVALENCE {e}")
    require(not errs, "jax twin outside jaxsim.TOLERANCES of the event loop")
    return {"arrivals": n, "p99_jax": twin["p99_s"],
            "p99_event": oracle["p99_s"]}


# ------------------------------------------------------------------ #
# --four-chip: sharded decode step vs one chip
# ------------------------------------------------------------------ #
def phase_four_chip(seed: int) -> dict:
    from jax.sharding import SingleDeviceSharding

    from repro.configs.base import get_config
    from repro.distributed import sharding
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models import model

    devs = jax.devices()
    require(len(devs) == 4, f"--four-chip needs 4 devices, found {len(devs)}")
    # Mosaic kernels cannot be partitioned by GSPMD: the sharded step
    # (and its one-chip twin) run the attention oracle
    ops.set_implementation("ref")
    cfg = get_config("stablelm_3b")
    mesh = make_mesh((2, 2), ("data", "model"))
    b = 8
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (b, PROMPT_LEN), 0, cfg.vocab_size,
                                 jnp.int32)
    one = SingleDeviceSharding(devs[0])
    params1 = _params(cfg, seed, shardings=one)
    _, cache = jax.jit(lambda p, bb: model.prefill(p, cfg, bb))(
        params1, {"tokens": prompts})
    tok = jnp.argmax(jax.random.normal(jax.random.PRNGKey(seed + 2),
                                       (b, cfg.vocab_size)), -1) \
        .astype(jnp.int32)
    pos = jnp.full((b,), PROMPT_LEN, jnp.int32)

    def step(p, t, c, q):
        return model.decode_step(p, cfg, t, c, q)

    c0, t0 = compile_seconds(), time.perf_counter()
    want, _ = jax.jit(step)(params1, tok, cache, pos)
    want = np.asarray(want, np.float32)
    print(f"  one-chip step {time.perf_counter() - t0:.1f}s "
          f"(compile {compile_seconds() - c0:.1f}s)")

    psh = sharding.params_sharding(params1, mesh, fsdp=False)
    csh = sharding.cache_sharding(cache, mesh, cfg, long_context=False)
    tsh = sharding.token_sharding(tok.shape, mesh)
    params4 = jax.device_put(params1, psh)
    per_dev = collections.Counter()
    for leaf in jax.tree.leaves(params4):
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(params4))
    print(f"  params {total / 1e9:.2f} GB; per device "
          + ", ".join(f"dev{d}={v / 1e9:.2f}GB"
                      for d, v in sorted(per_dev.items())))
    require(len(per_dev) == 4 and max(per_dev.values()) < 0.6 * total,
            "parameters are not spread over the four devices")
    cache4 = jax.device_put(cache, csh)
    tok4, pos4 = jax.device_put(tok, tsh), jax.device_put(pos, tsh)
    sharding.set_activation_batch_axes(sharding.batch_axes(mesh))
    try:
        c0, t0 = compile_seconds(), time.perf_counter()
        with mesh:
            got, _ = jax.jit(step, in_shardings=(psh, tsh, csh, tsh))(
                params4, tok4, cache4, pos4)
        got = np.asarray(got, np.float32)
    finally:
        sharding.set_activation_batch_axes(None)
    rel = _rel_l2(got, want)
    print(f"  mesh step {time.perf_counter() - t0:.1f}s "
          f"(compile {compile_seconds() - c0:.1f}s); logits rel L2 vs one "
          f"chip {rel:.3e} (tol {LOGIT_REL_TOL}), max abs "
          f"{float(np.max(np.abs(got - want))):.4f}")
    require(np.isfinite(got).all(), "non-finite sharded logits")
    require(rel <= LOGIT_REL_TOL, f"sharded logits disagree: {rel}")
    return {"rel_l2": rel, "per_device_gb": {d: v / 1e9
                                             for d, v in per_dev.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the (data=2, model=2) sharded decode "
                         "step and its one-chip comparison")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    phases = [("four_chip", phase_four_chip)] if args.four_chip else [
        ("control_plane", phase_control_plane),
        ("served", phase_served),
        ("simulator", phase_simulator)]
    for name, fn in phases:
        c0, t0 = compile_seconds(), time.perf_counter()
        print(f"[{name}]", flush=True)
        stats = fn(args.seed)
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s, compile "
              f"{compile_seconds() - c0:.1f}s: {stats}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
