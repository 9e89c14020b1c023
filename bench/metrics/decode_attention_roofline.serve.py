"""Decode attention's share of its roofline (%): the least time its
calls need (per call the larger of the K/V entries up to each active
request's position plus q and out over HBM bandwidth, and its FLOPs over
the bf16 peak) over the device time of the kernel's operations in the
trace (the instructions named after the kernel)."""
from bench.flops import least_seconds
from bench.trace import kernel_matches


def read(r):
    if r.trace is None:
        return None
    spent = r.trace.device_seconds(kernel_matches(r.extra["kernel"]))
    if spent <= 0:
        return None
    least = sum(count * least_seconds(f, b, r.peak)
                for f, b, count in r.extra["decode_attention"])
    return least / spent * 100.0
