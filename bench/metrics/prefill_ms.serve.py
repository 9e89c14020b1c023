"""Host time of one wave's prefill through ``ServingEngine.generate(steps=1)``
(prefill, merge into the engine's cache, first token read back), mean
over the window's waves (ms)."""


def read(r):
    spans = r.spans.spans.get("prefill")
    if not spans:
        return None
    return sum(t1 - t0 for t0, t1 in spans) / len(spans) * 1e3
