"""P99 over every request of the window of decision time minus due
time (ms). Whole-process pauses of the host come and go from run to
run and move it far (PERF.md), so it is read here and not bound end to
end."""
import numpy as np


def read(r):
    lat = r.spans.samples.get("decision_ms")
    return float(np.percentile(lat, 99)) if lat else None
