"""Random weights of a sparse-MoE decoder with a repeating pattern of
layer types (Mellum2's three sliding-window layers, then one full),
drawn on the device from a seed in one jitted call, in the layout
``repro.models.transformer`` serves and in the types it serves them in:
bf16 matrices, f32 router and norm scales. The reference draws the same
weights again from the same seed; it takes none from the program.

``cfg`` is a configuration file of ``bench/configs`` (transformers
config.json keys). Layout, for a pattern of p layer types repeated
P = L / p times: ``embed`` (V, d); ``blocks.layer{j}`` for j < p, each
stacked over the P periods, with ``norm1``/``norm2`` {scale} (P, d),
``attn`` {wq (P, d, H, hd), wk, wv (P, d, Hkv, hd), wo (P, H, hd, d)},
``moe`` {router (P, d, E), wi, wg (P, E, d, f), wo (P, E, f, d)};
``final_norm`` {scale} (d,); ``lm_head`` (d, V).

Norm scales are drawn around 0 (the norm multiplies by 1 + scale),
0.1 N(0, 1); matrices N(0, 1) / sqrt(fan-in), fan-in the axes a matrix
contracts (d for the embedding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.weights import _flatten


def pattern(cfg: dict) -> tuple:
    """The shortest run of ``layer_types`` that repeats over the served
    ``num_hidden_layers``."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return tuple(kinds[:p])
    raise ValueError("no repeating pattern")


def shapes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    v = cfg["vocab_size"]
    kinds = pattern(cfg)
    n = cfg["num_hidden_layers"] // len(kinds)
    layer = {
        "norm1": {"scale": (n, d)}, "norm2": {"scale": (n, d)},
        "attn": {"wq": (n, d, h, hd), "wk": (n, d, hkv, hd),
                 "wv": (n, d, hkv, hd), "wo": (n, h, hd, d)},
        "moe": {"router": (n, d, e), "wi": (n, e, d, f),
                "wg": (n, e, d, f), "wo": (n, e, f, d)}}
    return {
        "embed": (v, d),
        "blocks": {f"layer{j}": layer for j in range(len(kinds))},
        "final_norm": {"scale": (d,)},
        "lm_head": (d, v),
    }


def _fan_in(path: str, shape: tuple) -> int:
    if path == "embed":
        return shape[1]
    if path.endswith("attn/wo"):
        return shape[-3] * shape[-2]
    if path.endswith("attn/wq") or path.endswith("attn/wk") \
            or path.endswith("attn/wv"):
        return shape[-3]
    return shape[-2]          # router, expert matrices, lm_head


def _leaf(key, path: str, shape: tuple):
    if path.endswith("scale"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    scale = _fan_in(path, shape) ** -0.5
    if path.endswith("router"):
        return jax.random.normal(key, shape, jnp.float32) * scale
    w = jax.random.normal(key, shape, jnp.bfloat16)
    return w * jnp.asarray(scale, jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, frozen: tuple):
    return {path: _leaf(jax.random.fold_in(key, i), path, shape)
            for i, (path, shape) in enumerate(frozen)}


def make(cfg: dict, seed32: int):
    """The weights of ``cfg`` for a 31-bit seed, as a nested dict."""
    frozen = tuple(_flatten(shapes(cfg)))
    flat = _draw(jax.random.PRNGKey(seed32), frozen)
    tree: dict = {}
    for path, _ in frozen:
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = flat[path]
    return tree
