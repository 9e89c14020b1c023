"""The whole model's share of the chip's bf16 peak (%): the FLOPs the
window's prefill and decode tokens need, from shapes (matmuls plus
attention over each token's real context, the head on served tokens
only), over the window's seconds and the peak."""


def read(r):
    need = r.extra.get("model_flops")
    if not need or r.window_s <= 0:
        return None
    return need / r.window_s / r.peak["bf16_flops_per_s"] * 100.0
