"""The cell-independent half of the benchmark.

``main`` parses its arguments, refuses any machine without a TPU, turns
on JAX's persistent compilation cache, loads the cell named by
``--workload`` from ``BENCHMARK.json`` and hands it to the code of its
configuration's ``system`` (``bench/systems/<system>.py``). That code
builds its state, calls :meth:`Run.begin_window` and
:meth:`Run.end_window` around the measured window, then checks what the
window produced against its plain reference and returns an
:class:`Outcome`.

Output: informative lines first (device, set-up, compilations inside
the window), then each compared number beside its limit as the last
lines of standard error, and as the last line of standard output one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


# ------------------------------------------------------------------ #
# the cell, found by name
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    params: dict            # bench/cells/<cell>.json
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec or load_spec()
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"]
                                  in reported else [])]
    return Cell(name=name, chips=int(w["chips"]),
                config=_read_json(ROOT / conf["file"]),
                traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                params=_read_json(BENCH / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path, name: str):
    """Import a file whose name may hold dots (``bench/metrics/a.b.py``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ #
# host spans and samples
# ------------------------------------------------------------------ #
class Spans:
    """Host-clock spans and free samples a system's run records around
    their calls into the program. With ``annotate`` each span is
    also a ``TraceAnnotation`` in the profiler's trace, so idle gaps on
    the device can be attributed to what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: dict[str, list] = collections.defaultdict(list)
        self.samples: dict[str, list] = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench_{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans[name].append((t0, t1))

    def total(self, name: str) -> float:
        return sum(t1 - t0 for t0, t1 in self.spans.get(name, ()))


class GcWatch:
    """Durations of Python's garbage collections, kept while ``on``."""

    def __init__(self):
        import gc
        self.on = False
        self.passes: list = []
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.passes.append((info.get("generation"),
                                time.perf_counter() - self._t0))
            self._t0 = None


class CompileCounter:
    """Counts JAX tracings and backend compilations (cache loads
    included) through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.n += 1


# ------------------------------------------------------------------ #
# one run
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class Outcome:
    """What a system's run returns. ``metrics``: end-to-end values by name;
    ``checks``: compared number -> (value, limit), correct iff every
    value <= its limit; ``extra``: numbers the per-layer readers
    use."""

    attempted: int
    failed: int
    metrics: dict
    checks: dict
    extra: dict = dataclasses.field(default_factory=dict)


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.spans = Spans(annotate=trace)
        self.compiles = CompileCounter()
        self.gc = GcWatch()
        self.window_t0 = self.window_t1 = None
        self.setup_s = None
        self.compiles_in_window = None
        self.memory_peak_bytes = None
        self._ann = None
        self._c0 = 0

    def begin_window(self) -> None:
        import jax
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench_window")
            self._ann.__enter__()
        self._c0 = self.compiles.n
        self.gc.on = True
        self.window_t0 = time.perf_counter()
        self.setup_s = self.window_t0 - self.t_start
        print(f"setup_s: {self.setup_s:.6f}", flush=True)

    def end_window(self) -> None:
        import jax
        self.window_t1 = time.perf_counter()
        self.gc.on = False
        self.compiles_in_window = self.compiles.n - self._c0
        if self.trace:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.memory_peak_bytes = device_peak_bytes()
        print(f"window_s: {self.window_t1 - self.window_t0:.6f}")
        print(f"compiles_in_window: {self.compiles_in_window}")
        longest = max(self.gc.passes, key=lambda p: p[1], default=(None, 0.0))
        print(f"gc_in_window: {len(self.gc.passes)} passes, "
              f"{sum(d for _, d in self.gc.passes) * 1e3:.3f} ms, longest "
              f"{longest[1] * 1e3:.3f} ms (generation {longest[0]})",
              flush=True)

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0


def device_peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ------------------------------------------------------------------ #
# per-layer readings
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class Readings:
    """Everything a per-layer reader may read: the run's spans and
    samples, the system's extra numbers, the reduced device trace
    (``None`` without one) and the peaks of the device."""

    spans: Spans
    extra: dict
    trace: Any
    peak: dict
    window_s: float


def reader(name: str) -> Callable:
    """The ``read`` of another metric's reader, for a metric that reads
    the same quantity in cells that report another end-to-end metric."""
    return load_module(BENCH / "metrics" / f"{name}.py",
                       f"bench_metric_{name}").read


def read_per_layer(cell: Cell, readings: Readings) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{len(out)}")
        value = mod.read(readings)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ #
# entry point
# ------------------------------------------------------------------ #
def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The devices of this run, or exit when JAX finds no TPU or fewer
    than ``n`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < n:
        print(f"bench: the cell needs {n} chips; JAX found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devs[:n]


def enable_cache() -> str:
    """The program's fixed compile-cache directory inside the checkout
    (or ``JAX_COMPILATION_CACHE_DIR``), holding every program."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, devices: list,
            system: Optional[Callable] = None) -> tuple[dict, dict]:
    """Drive one run of ``cell``; returns (result line, checks). Tests
    call this directly with ``devices`` of their own choosing."""
    run = Run(cell, seed, seconds, trace, t_start)
    if system is None:
        system = load_module(BENCH / "systems" / f"{cell.config['system']}.py",
                             "bench_system").run
    out: Outcome = system(run)
    checks = {k: {"value": float(v), "limit": float(lim)}
              for k, (v, lim) in out.checks.items()}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line: dict = {"correct": correct, "attempted": int(out.attempted),
                  "failed": int(out.failed)}
    if trace:
        from bench import peaks
        from bench import trace as tr
        summary = tr.Summary.from_dir(TRACE_DIR, n_devices=len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        readings = Readings(spans=run.spans, extra=out.extra, trace=summary,
                            peak=peaks.peak_for(dev.device_kind),
                            window_s=run.window_s)
        line["metrics"] = read_per_layer(cell, readings)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
    else:
        # a cell file may name the quantity an end-to-end metric reports
        # where the metric's name differs from it
        named = cell.params.get("reports", {})
        metrics = dict(out.metrics, setup_s=run.setup_s)
        line["metrics"] = {
            m["name"]: {"value": float(metrics[named.get(m["name"],
                                                         m["name"])]),
                        "unit": m["unit"]}
            for m in cell.end_to_end}
    line["device"] = device
    line["checks"] = checks
    return line, checks


def main(argv, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = _parse(argv)
    cell = find_cell(args.workload)
    devices = require_chips(cell.chips)
    cache = enable_cache()
    d = devices[0]
    print(f"device: {d.platform} {d.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    line, checks = execute(cell, args.seed, args.seconds, bool(args.trace),
                           t_start, devices)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
