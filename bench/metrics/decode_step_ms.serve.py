"""Host time of one ``ServingEngine.step`` (decode step, tokens read
back), total over the window divided by the steps (ms)."""


def read(r):
    spans = r.spans.spans.get("decode_step")
    if not spans:
        return None
    return sum(t1 - t0 for t0, t1 in spans) / len(spans) * 1e3
