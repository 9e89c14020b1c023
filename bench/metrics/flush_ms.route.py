"""Host time of each ControlPlane call that returned decisions (table
build, upload, kernel, readback, post-processing, slot binding), total
over the window divided by the flushes (ms)."""


def read(r):
    spans = r.spans.spans.get("flush")
    if not spans:
        return None
    return sum(t1 - t0 for t0, t1 in spans) / len(spans) * 1e3
