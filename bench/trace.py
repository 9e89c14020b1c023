"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-kernel device time and the ``breakdown`` of the result line.

Planes whose name starts with ``/device:TPU`` are the chips. On each
chip the ``XLA Ops`` line holds one event per operation executed, named
by its HLO instruction (``%decode_attention.7 = bf16[...] custom-call(...)``:
a Pallas kernel's instruction carries the name of the kernel's jitted
function), and the ``XLA Modules`` line one event per program run
(``jit_routing_guard(<fingerprint>)``); an operation belongs to the
program run that encloses it. A while loop's event encloses the events
of its body. Busy time is the union of the operations' intervals inside
the traced window, averaged over the chips used. The window is the
host's ``bench_window`` annotation (host and chip events share one
clock in the trace), otherwise the first to the last device event.

Host threads carry the benchmark's own ``bench_<span>`` annotations; an
idle gap on the device is named after the innermost such span that
covers its middle ("host" where none does).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Callable, Optional

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    device: str
    name: str
    module: str
    start_ns: float
    dur_ns: float


def short_name(name: str) -> str:
    """``%copy.4 = s32[...] copy(...)`` -> ``copy.4``;
    ``jit_f(123)`` -> ``jit_f``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Summary:
    def __init__(self, ops: list, host: list, window: Optional[tuple],
                 n_devices: int):
        self.ops = ops
        self.host = host            # (name, start_ns, end_ns) bench spans
        devices = sorted({o.device for o in ops})[:max(1, n_devices)]
        self.devices = devices
        if window is None and ops:
            window = (min(o.start_ns for o in ops),
                      max(o.start_ns + o.dur_ns for o in ops))
        self.window = window or (0.0, 0.0)
        w0, w1 = self.window
        self.window_s = (w1 - w0) * 1e-9
        self.busy = {}
        for dev in devices:
            iv = [(max(o.start_ns, w0), min(o.start_ns + o.dur_ns, w1))
                  for o in ops if o.device == dev]
            self.busy[dev] = _merge([(s, e) for s, e in iv if e > s])
        per_dev = [sum(e - s for s, e in iv) for iv in self.busy.values()]
        self.busy_s = (sum(per_dev) / len(per_dev) * 1e-9) if per_dev else 0.0

    # -------------------------------------------------------------- #
    @classmethod
    def from_file(cls, path: str, n_devices: int = 1) -> "Summary":
        from jax._src.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops, host, window = [], [], None
        for plane in pd.planes:
            pname = plane.name
            if pname.startswith("/device:TPU"):
                lines = {ln.name: ln for ln in plane.lines}
                mods = sorted((float(e.start_ns), float(e.duration_ns),
                               short_name(e.name))
                              for e in (lines[MODULES_LINE].events
                                        if MODULES_LINE in lines else ()))
                starts = [m[0] for m in mods]
                for ev in (lines[OPS_LINE].events if OPS_LINE in lines
                           else ()):
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    k = bisect.bisect_right(starts, s) - 1
                    module = mods[k][2] if k >= 0 and \
                        s < mods[k][0] + mods[k][1] else ""
                    ops.append(Op(pname, short_name(ev.name), module, s, d))
            elif pname.startswith("/host:"):
                for ln in plane.lines:
                    for ev in ln.events:
                        if ev.name.startswith("bench_"):
                            s, d = float(ev.start_ns), float(ev.duration_ns)
                            if ev.name == WINDOW:
                                window = (s, s + d)
                            else:
                                host.append((ev.name[6:], s, s + d))
        return cls(ops, host, window, n_devices)

    @classmethod
    def from_dir(cls, trace_dir, n_devices: int = 1) -> "Summary":
        paths = sorted(glob.glob(os.path.join(
            str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_file(paths[-1], n_devices)

    # -------------------------------------------------------------- #
    def _in_window(self, o: Op) -> bool:
        w0, w1 = self.window
        return o.start_ns < w1 and o.start_ns + o.dur_ns > w0

    def select(self, pred: Callable[[Op], bool]) -> list:
        return [o for o in self.ops if o.device in self.devices
                and self._in_window(o) and pred(o)]

    def device_seconds(self, pred: Callable[[Op], bool]) -> float:
        """Device time of the matching operations, summed over the chips
        used and divided by their number."""
        sel = self.select(pred)
        return sum(o.dur_ns for o in sel) * 1e-9 / max(1, len(self.devices))

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def gaps(self):
        """Idle gaps of the first chip inside the window:
        (start_ns, end_ns)."""
        if not self.devices:
            return []
        w0, w1 = self.window
        busy = self.busy[self.devices[0]]
        out, t = [], w0
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if w1 > t:
            out.append((t, w1))
        return out

    def host_label(self, t: float) -> str:
        best = None
        for name, s, e in self.host:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "host"

    def breakdown(self, top: int = 10) -> dict:
        by_op = collections.Counter()
        for o in self.select(lambda o: True):
            key = f"{o.module}/{o.name}" if o.module else o.name
            by_op[key] += o.dur_ns * 1e-9
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in by_op.most_common(top)],
                "idle_gaps": [[self.host_label((s + e) / 2), (e - s) * 1e-9]
                              for s, e in gaps]}


def module_matches(fragment: str) -> Callable[[Op], bool]:
    """Operations of the jitted program whose module name holds
    ``fragment``."""
    return lambda o: fragment in o.module


def kernel_matches(kernel: str) -> Callable[[Op], bool]:
    """The instructions of a Pallas kernel: named ``<kernel>`` or
    ``<kernel>.<n>`` after the kernel's jitted function."""
    return lambda o: o.name == kernel or o.name.startswith(kernel + ".")
