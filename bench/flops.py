"""Operations and bytes a decoder step needs, from shapes and positions
only. A later kernel that reads or computes less is scored against the
same need.

``cfg`` is a configuration file of ``bench/configs`` (the keys of a
transformers config.json): ``hidden_size`` d, ``num_attention_heads`` H,
``num_key_value_heads`` Hkv, head size hd = d / H, ``intermediate_size``
f, ``num_hidden_layers`` L, ``vocab_size`` V.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], d // h,
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def body_flops_per_token(cfg: dict) -> int:
    """Matmul FLOPs of one token through every layer (q, k, v, o and the
    gated MLP's three matrices), 2 per multiply-add."""
    d, h, hkv, hd, f, n_layers, _ = _dims(cfg)
    per_layer = d * h * hd * 2 + d * hkv * hd * 2 + 3 * d * f
    return 2 * n_layers * per_layer


def head_flops(cfg: dict) -> int:
    """FLOPs of the output projection for one token."""
    d, *_, vocab = _dims(cfg)
    return 2 * d * vocab


def attention_flops(cfg: dict, context: int) -> int:
    """FLOPs of one query attending to ``context`` keys in every layer:
    q.k and p.v, 2 per multiply-add each."""
    d, h, hkv, hd, f, n_layers, _ = _dims(cfg)
    return n_layers * 4 * h * hd * context


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """One request's prefill: every prompt token through the body and
    attending causally, the head on the last token only."""
    return (prompt_len * body_flops_per_token(cfg)
            + sum(attention_flops(cfg, p + 1) for p in range(prompt_len))
            + head_flops(cfg))


def decode_flops(cfg: dict, position: int) -> int:
    """One generated token whose query sits at ``position``."""
    return (body_flops_per_token(cfg) + attention_flops(cfg, position + 1)
            + head_flops(cfg))


def decode_attention_need(cfg: dict, position: int,
                          bytes_per_el: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) one layer's decode attention needs for one request
    whose query sits at ``position``: the K and V entries of positions
    0..position, plus the query and the output."""
    d, h, hkv, hd, *_ = _dims(cfg)
    ctx = position + 1
    flops = 4 * h * hd * ctx
    nbytes = (2 * ctx * hkv * hd + 2 * h * hd) * bytes_per_el
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
