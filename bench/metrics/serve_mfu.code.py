"""The whole MoE model's share of the chip's bf16 peak (%), as
``serve_mfu`` reads it: the window's FLOPs from shapes, counting the
routed experts of each token only (``bench/flops_moe.py``)."""
from bench.harness import reader

read = reader("serve_mfu")
