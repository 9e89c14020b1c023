"""The grouped expert matmul's share of its roofline (%): the least time
its calls need (per call the larger of its FLOPs over the bf16 peak and
its bytes over HBM bandwidth; ``bench/flops_moe.py``: in prefill every
expert's matrices and the rows, in decode the experts the engine's
counters saw touched and the rows) over the device time of the kernel's
instructions in the trace."""
from bench.flops import least_seconds
from bench.trace import kernel_matches


def read(r):
    if r.trace is None or "gmm_kernel" not in r.extra:
        return None
    spent = r.trace.device_seconds(kernel_matches(r.extra["gmm_kernel"]))
    if spent <= 0:
        return None
    least = sum(count * least_seconds(f, b, r.peak)
                for f, b, count in r.extra[r.extra["gmm_kernel"]])
    return least / spent * 100.0
