"""The program's profiler spans (``repro.tracing``) and their reduction
(``bench/program_trace.py``).

A ``ControlPlane`` flush on the fused path (the oracle on the CPU) and a
small ``ServingEngine`` are profiled under ``jax.profiler.trace``; the
trace is read back and the spans are checked by name, nesting and args.
The reduction's helpers are checked on hand-made events, as
``bench/selfcheck.py`` checks the rest of the reduction.
"""
from __future__ import annotations

import ast
import math
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench import program_trace
from bench.trace import WINDOW, Op
from repro import tracing
from repro.configs.base import get_config, reduced
from repro.control import ControlPlane
from repro.control.admission import AdmissionConfig
from repro.core.catalogue import paper_cluster
from repro.core.scheduler import Request
from repro.models import model
from repro.serving.engine import ServingEngine

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
FLUSH_CHILDREN = ("laimr.policy.rates", "laimr.policy.upload",
                  "laimr.kernel.launch", "laimr.policy.readback",
                  "laimr.plane.bind")
STEP_CHILDREN = ("laimr.engine.dispatch", "laimr.engine.readback")
SPANS = ("laimr.plane.flush",) + FLUSH_CHILDREN + (
    "laimr.engine.step",) + STEP_CHILDREN + ("laimr.engine.merge",)
POLICIES = ("route_best", "guarded_alg1", "safetail", "reliable", "hybrid")


def _plane(policy: str) -> ControlPlane:
    cfg = AdmissionConfig(backend="pallas", policy=policy, window=0.05,
                          max_batch=8)
    return ControlPlane(paper_cluster(), config=cfg, policy=policy)


def _flush(plane: ControlPlane, n: int, t: float) -> list:
    """Submit ``n`` requests, one for each deployment in turn, at ``t``
    and flush the window."""
    deps = list(plane.cluster)
    for j in range(n):
        d = deps[j % len(deps)]
        plane.submit(Request(model=d.model.name, quality=d.quality,
                             arrival=t), t)
    return plane.flush(t)


def _profile(tmp, body) -> program_trace.ProgramSummary:
    """Profile ``body`` inside the benchmark's window annotation."""
    with jax.profiler.trace(str(tmp)):
        with jax.profiler.TraceAnnotation(WINDOW):
            body()
    return program_trace.ProgramSummary.from_dir(tmp)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One guarded_alg1 flush of 3 requests, then one wave of a small
    engine (1 prompt into 2 slots, so the prefill cache is merged, and
    one decode step), both warmed up before the profile."""
    plane = _plane("guarded_alg1")
    _flush(plane, 3, 0.0)
    cfg = reduced(get_config("stablelm_3b"))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    prompts = jnp.ones((1, 8), jnp.int32)
    eng.generate(prompts, steps=2)
    out = {}

    def body():
        out["decisions"] = _flush(plane, 3, 1.0)
        out["tokens"] = eng.generate(prompts, steps=2).tokens

    summary = _profile(tmp_path_factory.mktemp("profile"), body)
    return summary, out


def _by_name(summary, name):
    return [p for p in summary.program if p[0] == name]


def test_every_span_is_recorded(traced):
    summary, out = traced
    assert len(out["decisions"]) == 3 and out["tokens"].shape == (1, 2)
    names = {p[0] for p in summary.program}
    assert names == set(SPANS)
    for name in SPANS:
        assert len(_by_name(summary, name)) == 1, name


def test_flush_children_nest_inside_the_flush(traced):
    summary, _ = traced
    (flush,) = _by_name(summary, "laimr.plane.flush")
    inside = [p[0] for p in summary.nested(flush)]
    assert inside == list(FLUSH_CHILDREN)
    # in order and without overlap: rates, upload, launch, readback, bind
    spans = [p for p in summary.program if p[0] in FLUSH_CHILDREN]
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))


def test_flush_args_are_read_back(traced):
    summary, _ = traced
    (flush,) = _by_name(summary, "laimr.plane.flush")
    # the warm-up was the plane's first flush
    assert flush[3] == {"flush": 2, "rows": 3}
    assert all(p[3] == {} for p in summary.program if p is not flush)


def test_step_children_nest_inside_the_step(traced):
    summary, _ = traced
    (step,) = _by_name(summary, "laimr.engine.step")
    assert [p[0] for p in summary.nested(step)] == list(STEP_CHILDREN)
    (merge,) = _by_name(summary, "laimr.engine.merge")
    assert merge[2] <= step[1]


def test_readings_of_a_cpu_profile(traced):
    summary, _ = traced
    got = program_trace.readings(summary)
    # a CPU profile has no chip, so no engine_idle_ms
    assert set(got) == {"flush_rates_us", "flush_upload_us",
                        "flush_launch_us", "flush_readback_us",
                        "flush_bind_us", "step_dispatch_ms",
                        "step_readback_ms", "prefill_merge_ms"}
    assert all(v > 0 for v in got.values())
    (flush,) = _by_name(summary, "laimr.plane.flush")
    children = sum(got[k] for k in program_trace.FLUSH_CHILDREN)
    assert children * 1e3 <= flush[2] - flush[1]


@pytest.mark.parametrize("policy", POLICIES)
def test_every_fused_policy_has_the_five_phases(policy, tmp_path):
    plane = _plane(policy)
    _flush(plane, 2, 0.0)
    summary = _profile(tmp_path, lambda: _flush(plane, 5, 1.0))
    (flush,) = _by_name(summary, "laimr.plane.flush")
    assert flush[3] == {"flush": 2, "rows": 5}
    assert [p[0] for p in summary.nested(flush)] == list(FLUSH_CHILDREN)


def test_vmap_backend_has_rates_and_bind_only(tmp_path):
    cfg = AdmissionConfig(backend="vmap", policy="guarded_alg1")
    plane = ControlPlane(paper_cluster(), config=cfg, policy="guarded_alg1")
    _flush(plane, 2, 0.0)
    summary = _profile(tmp_path, lambda: _flush(plane, 2, 1.0))
    (flush,) = _by_name(summary, "laimr.plane.flush")
    assert [p[0] for p in summary.nested(flush)] == [
        "laimr.policy.rates", "laimr.plane.bind"]


def test_spans_in_the_source_are_the_documented_ones():
    """Every ``span("...")`` call under ``src/repro`` names one of the
    spans, each span is placed, and the module docstring lists each."""
    placed = []
    for path in SRC.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "span" and node.args
                    and isinstance(node.args[0], ast.Constant)):
                placed.append(tracing.PREFIX + node.args[0].value)
    assert set(placed) == set(SPANS)
    for name in SPANS:
        assert f"``{name}``" in tracing.__doc__, name


def test_span_is_a_trace_annotation():
    ann = tracing.span("plane.flush", flush=1, rows=2)
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    with ann:
        pass


# ------------------------------------------------------------------ #
# the reduction, on hand-made events
# ------------------------------------------------------------------ #
def _hand_made() -> program_trace.ProgramSummary:
    ops = [Op("/device:TPU:0", "a", "jit_routing_guard", 100, 50),
           Op("/device:TPU:0", "c", "jit_routing_guard", 400, 100),
           Op("/device:TPU:0", "d", "jit_other", 950, 100)]
    host = [("flush", 180, 420), ("wait", 500, 900)]
    program = [("laimr.plane.flush", 160, 430, {"flush": 7, "rows": 2}),
               ("laimr.policy.upload", 200, 300, {}),
               ("laimr.kernel.launch", 300, 420, {}),
               ("laimr.plane.flush", 1100, 1200, {"flush": 8, "rows": 1})]
    return program_trace.ProgramSummary(ops, host, (100.0, 1000.0),
                                        n_devices=1, program=program)


def test_span_ns_counts_spans_inside_the_window():
    s = _hand_made()
    # the second flush starts after the window closes
    assert s.span_ns("laimr.plane.flush") == [270.0]
    assert s.span_ns("laimr.policy.upload") == [100.0]
    assert s.span_ns("laimr.engine.step") == []


def test_idle_under_subtracts_the_busy_chip():
    s = _hand_made()
    # busy [100,150) [400,500) [950,1000): the flush [160,430) is busy
    # for 30 of its 270 ns, the launch [300,420) for 20 of 120
    assert math.isclose(s.idle_under("laimr.plane.flush"), 240.0)
    assert math.isclose(s.idle_under("laimr.kernel.launch"), 100.0)
    assert s.idle_under("laimr.policy.upload") == 100.0


def test_gap_labels_name_the_program_span():
    s = _hand_made()
    # gaps (150,400), (500,950): the first's middle (275) lies in the
    # bench span "flush" and the program's upload; the second's only in
    # "wait", whose label stays as it was
    labels = [name for name, _ in s.breakdown()["idle_gaps"]]
    assert labels == ["wait", "flush/laimr.policy.upload"]
    assert s.host_label(350) == "flush/laimr.kernel.launch"
    assert s.host_label(170) == "host/laimr.plane.flush"


def test_readings_of_hand_made_spans():
    got = program_trace.readings(_hand_made())
    assert got == pytest.approx({"flush_upload_us": 0.1,
                                 "flush_launch_us": 0.12})


def test_reduction_without_program_spans_is_the_benchmarks():
    """With no program span (a trace of a program without them) the
    labels are the benchmark's own and nothing is read."""
    s = _hand_made()
    bare = program_trace.ProgramSummary(s.ops, s.host, s.window, 1)
    gaps = bare.breakdown()["idle_gaps"]
    assert [name for name, _ in gaps] == ["wait", "flush"]
    assert [d for _, d in gaps] == pytest.approx([450e-9, 250e-9])
    assert program_trace.readings(bare) == {}
    assert bare.busy_s == s.busy_s
