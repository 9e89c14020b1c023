"""The program's own spans in a profiler trace, beside the benchmark's.

The program marks its phases with ``laimr.<name>`` profiler spans
(``repro.tracing``): inside ``ControlPlane.flush`` the policy's rate
table, the upload, the kernel's launch, the readback and the binding;
inside ``ServingEngine.step`` the dispatch and the readback; in
``generate`` the merge of the prefill cache. :class:`ProgramSummary` is
``trace.Summary`` that also keeps those host events, as ``program``:
``(name, start_ns, end_ns, args)``, the args read from the event's
stats. It adds :meth:`~ProgramSummary.span_ns` and
:meth:`~ProgramSummary.idle_under`, and names an idle gap of the device
``<bench span>/<program span>`` where a program span covers it.
:func:`readings` turns a summary into the per-layer quantities these
spans give.

Run as a script it runs one cell with the profiler on, as ``bench/run.py
--trace 1`` does, and prints one JSON line: the cell's per-layer metrics,
its checks, :func:`readings`, every program span's count, mean, longest
and device idle, the children of the longest flush and step, and the
breakdown with the idle gaps named down to the program span::

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
if __package__ in (None, ""):
    sys.path[:0] = [os.path.dirname(HERE),
                    os.path.join(os.path.dirname(HERE), "src")]

from bench import trace  # noqa: E402

PROGRAM = "laimr."
FLUSH = "laimr.plane.flush"
FLUSH_CHILDREN = {"flush_rates_us": "laimr.policy.rates",
                  "flush_upload_us": "laimr.policy.upload",
                  "flush_launch_us": "laimr.kernel.launch",
                  "flush_readback_us": "laimr.policy.readback",
                  "flush_bind_us": "laimr.plane.bind"}
STEP = "laimr.engine.step"
STEP_CHILDREN = {"step_dispatch_ms": "laimr.engine.dispatch",
                 "step_readback_ms": "laimr.engine.readback"}
MERGE = "laimr.engine.merge"


def program_spans(path: str) -> list:
    """Host events of the trace whose name starts with ``laimr.``:
    (name, start_ns, end_ns, args)."""
    from jax._src.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(PROGRAM):
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    out.append((ev.name, s, s + d, dict(ev.stats)))
    return out


class ProgramSummary(trace.Summary):
    def __init__(self, ops: list, host: list, window, n_devices: int,
                 program=()):
        super().__init__(ops, host, window, n_devices)
        self.program = sorted(program, key=lambda p: p[1])

    @classmethod
    def from_file(cls, path: str, n_devices: int = 1) -> "ProgramSummary":
        base = trace.Summary.from_file(path, n_devices)
        return cls(base.ops, base.host, base.window, n_devices,
                   program=program_spans(path))

    # -------------------------------------------------------------- #
    def spans(self, name: str) -> list:
        """The program spans named ``name`` that start inside the
        window, in order."""
        w0, w1 = self.window
        return [p for p in self.program if p[0] == name and w0 <= p[1] < w1]

    def span_ns(self, name: str) -> list:
        """Durations (ns) of the named spans inside the window."""
        return [e - s for _, s, e, _ in self.spans(name)]

    def idle_under(self, name: str) -> float:
        """The first chip's idle time (ns) inside the named spans."""
        if not self.devices:
            return 0.0
        busy = self.busy[self.devices[0]]
        starts = [s for s, _ in busy]
        idle = 0.0
        for _, s, e, _ in self.spans(name):
            covered = 0.0
            k = max(bisect.bisect_right(starts, s) - 1, 0)
            while k < len(busy) and busy[k][0] < e:
                covered += max(0.0, min(e, busy[k][1]) - max(s, busy[k][0]))
                k += 1
            idle += (e - s) - covered
        return idle

    def program_label(self, t: float):
        """The innermost program span covering ``t``, or None."""
        best = None
        for name, s, e, _ in self.program:
            if s > t:
                break
            if t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else None

    def host_label(self, t: float) -> str:
        label = super().host_label(t)
        inner = self.program_label(t)
        return f"{label}/{inner}" if inner else label

    def nested(self, parent: tuple) -> list:
        """The program spans inside ``parent`` (one of ``program``)."""
        _, s0, e0, _ = parent
        return [p for p in self.program
                if p is not parent and s0 <= p[1] and p[2] <= e0]


def _per(summary: ProgramSummary, child: str, parent: str,
         scale: float):
    n = len(summary.spans(parent))
    d = summary.span_ns(child)
    return sum(d) / n * scale if n and d else None


def readings(s: ProgramSummary) -> dict:
    """The quantities the program spans give: of a flush, each phase's
    time in the window over the flushes (us); of a decode step, the
    dispatch and the readback over the steps and the chip's idle time
    under the steps (ms); the prefill cache's merge over the waves
    (ms). A quantity whose spans are absent is left out."""
    out = {k: _per(s, v, FLUSH, 1e-3) for k, v in FLUSH_CHILDREN.items()}
    out.update({k: _per(s, v, STEP, 1e-6) for k, v in STEP_CHILDREN.items()})
    merges = s.span_ns(MERGE)
    out["prefill_merge_ms"] = (sum(merges) / len(merges) * 1e-6
                               if merges else None)
    steps = len(s.spans(STEP))
    out["engine_idle_ms"] = (s.idle_under(STEP) / steps * 1e-6
                             if steps and s.devices else None)
    return {k: v for k, v in out.items() if v is not None}


def span_table(s: ProgramSummary) -> dict:
    """Every program span in the window: count, mean and longest (us),
    and the first chip's idle time under it, per span (us)."""
    out = {}
    for name in sorted({p[0] for p in s.program}):
        d = s.span_ns(name)
        if d:
            out[name] = {"count": len(d), "mean_us": sum(d) / len(d) * 1e-3,
                         "max_us": max(d) * 1e-3,
                         "idle_us": s.idle_under(name) / len(d) * 1e-3}
    return out


def longest(s: ProgramSummary, name: str):
    """The longest span named ``name`` in the window, with its args and
    the spans inside it (us)."""
    spans = s.spans(name)
    if not spans:
        return None
    top = max(spans, key=lambda p: p[2] - p[1])
    return {"us": (top[2] - top[1]) * 1e-3, "args": top[3],
            "inside": [[p[0], (p[2] - p[1]) * 1e-3] for p in s.nested(top)]}


# ------------------------------------------------------------------ #
def main(argv) -> int:
    from bench import harness, peaks
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_cache()
    run = harness.Run(cell, args.seed, args.seconds, True, T_START)
    system = harness.load_module(
        harness.BENCH / "systems" / f"{cell.config['system']}.py",
        "bench_system").run
    out = system(run)
    s = ProgramSummary.from_dir(harness.TRACE_DIR, n_devices=len(devices))
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    dev = devices[0]
    r = harness.Readings(spans=run.spans, extra=out.extra, trace=s,
                         peak=peaks.peak_for(dev.device_kind),
                         window_s=run.window_s)
    checks = {k: [float(v), float(lim)] for k, (v, lim) in out.checks.items()}
    line = {"workload": cell.name, "seed": args.seed,
            "device": f"{dev.platform} {dev.device_kind} x{len(devices)}",
            "correct": bool(checks) and all(v <= lim for v, lim
                                             in checks.values()),
            "metrics": harness.read_per_layer(cell, r),
            "program": readings(s), "spans": span_table(s),
            "longest": {n: longest(s, n) for n in (FLUSH, STEP)},
            "breakdown": s.breakdown(), "checks": checks}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
