"""CPU self-check of the yardstick: ``python -m bench.selfcheck``.

1. The FLOP and byte functions against hand counts at one small shape.
2. The trace reduction on hand-made operations: the union of
   overlapping intervals, clipping to the window, idle gaps and the
   host span they are named after.
3. The trace reduction on a small trace recorded on one TPU v5e
   (``bench/testdata/route.xplane.pb``: a few launches of the routing
   kernel), against the numbers read from it when it was recorded, which
   agree with its XLA Modules line (49 runs of ``jit_routing_guard``).
"""
from __future__ import annotations

import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(HERE))

from bench import flops, trace  # noqa: E402

SMALL = {"hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 2, "intermediate_size": 16,
         "num_hidden_layers": 2, "vocab_size": 32}
RECORDED = os.path.join(HERE, "testdata", "route.xplane.pb")
# the recorded trace, as read when it was recorded: a 0.37 s window of a
# route cell over two deployments with 49 launches of the routing kernel
# (its program has 30 operations a launch), the chip busy 213.7 us of it
RECORDED_EXPECT = {'devices': 1, 'routing_launches': 49, 'routing_program_ops': 1470, 'busy_ns': 213738, 'window_ns': 370410827, 'kernel_ns': 71481}


def check_flops() -> None:
    # per layer: q,o 8*2*4 each, k,v 8*2*4 each, MLP 3*8*16; x2 for FMA
    assert flops.body_flops_per_token(SMALL) == 2 * 2 * (64 * 4 + 384)
    assert flops.head_flops(SMALL) == 2 * 8 * 32
    # 2 layers x (q.k + p.v) x 2 heads x 4 dims x 3 keys x 2
    assert flops.attention_flops(SMALL, 3) == 2 * 2 * 2 * 4 * 3 * 2
    # two prompt tokens attend to 1 and 2 keys; the head on the last
    assert flops.prefill_flops(SMALL, 2) == 2 * 2560 + 64 * 1 + 64 * 2 + 512
    assert flops.decode_flops(SMALL, 4) == 2560 + 64 * 5 + 512
    f, b = flops.decode_attention_need(SMALL, 2)
    assert f == 4 * 2 * 4 * 3
    # K and V: 3 positions x 2 heads x 4 dims; q and out: 2 x 4; bf16
    assert b == (2 * 3 * 2 * 4 + 2 * 2 * 4) * 2
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(50.0, 30.0, peak) == 3.0
    assert flops.least_seconds(500.0, 30.0, peak) == 5.0


def check_reduction() -> None:
    op = trace.Op
    ops = [op("/device:TPU:0", "a", "jit_routing_guard", 100, 50),
           op("/device:TPU:0", "b", "jit_other", 120, 60),   # overlaps a
           op("/device:TPU:0", "c", "jit_routing_guard", 400, 100),
           op("/device:TPU:0", "d", "jit_other", 950, 100),  # clipped
           op("/device:TPU:1", "e", "jit_other", 0, 1000)]   # not used
    host = [("flush", 180, 420), ("wait", 500, 900), ("submit", 600, 700)]
    s = trace.Summary(ops, host, (100.0, 1000.0), n_devices=1)
    assert s.devices == ["/device:TPU:0"]
    assert math.isclose(s.window_s, 900e-9)
    # busy: [100,180) + [400,500) + [950,1000) = 80 + 100 + 50
    assert math.isclose(s.busy_s, 230e-9)
    assert math.isclose(s.idle_share(), 1 - 230 / 900)
    assert s.gaps() == [(180, 400), (500, 950)]
    g = s.breakdown()["idle_gaps"]
    assert [name for name, _ in g] == ["wait", "flush"], g
    assert math.isclose(g[0][1], 450e-9)
    guard = trace.module_matches("routing_guard")
    assert math.isclose(s.device_seconds(guard), 150e-9)
    top = s.breakdown()["device_ops"][0]
    assert top[0] == "jit_routing_guard/c" and math.isclose(top[1], 1e-7)


def check_recorded() -> None:
    if RECORDED_EXPECT is None or not os.path.exists(RECORDED):
        raise SystemExit(f"missing recorded trace {RECORDED}")
    s = trace.Summary.from_file(RECORDED)
    got = {"devices": len(s.devices),
           "routing_launches": len(s.select(trace.kernel_matches(
               "routing_guard"))),
           "routing_program_ops": len(s.select(trace.module_matches(
               "routing_guard"))),
           "busy_ns": round(s.busy_s * 1e9),
           "window_ns": round(s.window_s * 1e9),
           "kernel_ns": round(s.device_seconds(trace.kernel_matches(
               "routing_guard")) * 1e9)}
    assert got == RECORDED_EXPECT, (got, RECORDED_EXPECT)


def main() -> int:
    check_flops()
    check_reduction()
    check_recorded()
    print("bench selfcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
