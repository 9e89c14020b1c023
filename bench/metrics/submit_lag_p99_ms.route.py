"""P99 over the window's requests of submit time minus due time (ms):
how late the single-threaded load generator ran."""
import numpy as np


def read(r):
    lags = r.spans.samples.get("submit_lag_ms")
    return float(np.percentile(lags, 99)) if lags else None
