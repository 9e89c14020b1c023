"""Device time of the routing kernel per launch (us), from the trace:
the instructions named after the Pallas kernel."""
from bench.trace import kernel_matches


def read(r):
    if r.trace is None:
        return None
    ops = r.trace.select(kernel_matches(r.extra["kernel"]))
    if not ops:
        return None
    return sum(o.dur_ns for o in ops) / len(ops) * 1e-3
