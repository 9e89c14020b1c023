"""Operations and bytes a sparse-MoE decoder with sliding and full
attention needs, from shapes, positions and the routing counters only.
A later kernel that reads or computes less is scored against the same
need.

``cfg`` is a configuration file of ``bench/configs`` (the keys of a
transformers config.json): ``hidden_size`` d, ``num_attention_heads`` H,
``num_key_value_heads`` Hkv, ``head_dim`` hd, ``num_experts`` E,
``num_experts_per_tok`` k, ``moe_intermediate_size`` f,
``num_hidden_layers`` L with ``layer_types``, ``sliding_window`` W,
``vocab_size`` V. Every expert is a gated MLP: three (d, f) matrices.
"""
from __future__ import annotations

BYTES = 2             # bf16 weights and activations


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"])


def layer_kinds(cfg: dict) -> list:
    """The served layers' types, in order."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def _visible(cfg: dict, kind: str, position: int) -> int:
    """Keys a query at ``position`` attends to in a layer of ``kind``."""
    if kind == "sliding_attention":
        return min(position + 1, cfg["sliding_window"])
    return position + 1


def body_flops_per_token(cfg: dict) -> int:
    """Matmul FLOPs of one token through every layer: q, k, v, o, the
    router and its k experts' three matrices (2 per multiply-add)."""
    d, h, hkv, hd, e, k, f = _dims(cfg)
    per_layer = d * h * hd * 2 + d * hkv * hd * 2 + d * e + 3 * k * d * f
    return 2 * cfg["num_hidden_layers"] * per_layer


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: dict, position: int) -> int:
    """One query at ``position`` through every layer's attention: q.k
    and p.v over the keys it sees."""
    _, h, _, hd, *_ = _dims(cfg)
    return sum(4 * h * hd * _visible(cfg, kind, position)
               for kind in layer_kinds(cfg))


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """One request's prefill, the head on the last token only."""
    return (prompt_len * body_flops_per_token(cfg)
            + sum(attention_flops(cfg, p) for p in range(prompt_len))
            + head_flops(cfg))


def decode_flops(cfg: dict, position: int) -> int:
    """One generated token whose query sits at ``position``."""
    return (body_flops_per_token(cfg) + attention_flops(cfg, position)
            + head_flops(cfg))


def decode_attention_need(cfg: dict, kind: str,
                          position: int) -> tuple[int, int]:
    """(FLOPs, bytes) one layer of ``kind`` needs to decode one request
    whose query sits at ``position``: the K and V entries it sees (at
    most W on a sliding layer), the query and the output."""
    _, h, hkv, hd, *_ = _dims(cfg)
    ctx = _visible(cfg, kind, position)
    return 4 * h * hd * ctx, (2 * ctx * hkv * hd + 2 * h * hd) * BYTES


def gmm_prefill_need(cfg: dict, tokens: int) -> list:
    """(FLOPs, bytes) of each of one layer's three ``moe_gmm`` calls
    (gate, up, down) over the k rows of ``tokens`` prefill tokens: every
    expert's matrix read once, the rows read and written once."""
    d, *_, e, k, f = _dims(cfg)
    rows = tokens * k
    call = (2 * rows * d * f, (e * d * f + rows * (d + f)) * BYTES)
    return [call] * 3


def gmm_decode_need(cfg: dict, rows: float, touched: float) -> list:
    """(FLOPs, bytes) of each of one layer's three ``moe_gmm`` calls in a
    decode step that routed ``rows`` rows to ``touched`` distinct
    experts: only those experts' matrices are read."""
    d, *_, f = _dims(cfg)
    call = (2 * rows * d * f, (touched * d * f + rows * (d + f)) * BYTES)
    return [call] * 3
