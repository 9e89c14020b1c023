"""Decode attention's share of its roofline in the MoE cell (%), as
``decode_attention_roofline.serve`` reads it, with this cell's need: the
K/V entries of 4 KV heads a query sees, at most the window's 1024 on a
sliding layer (``bench/flops_moe.decode_attention_need``)."""
from bench.harness import reader

read = reader("decode_attention_roofline.serve")
