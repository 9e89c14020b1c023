"""Random decoder weights drawn on the device from a seed, in one jitted
call, in the layout ``repro.models.transformer`` serves and in the type
it serves them in (bf16 matrices, f32 norms). The reference draws the
same weights again from the same seed; it takes none from the program.

Layout: ``embed`` (V, d); ``blocks.layer0`` stacked over L layers with
``norm1``/``norm2`` {scale, bias} (L, d), ``attn`` {wq, wk, wv (L, d, H,
hd), wo (L, H, hd, d)}, ``mlp`` {wi, wg (L, d, f), wo (L, f, d)};
``final_norm``; ``lm_head`` (d, V).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, f = cfg["num_key_value_heads"], cfg["intermediate_size"]
    n, v, hd = cfg["num_hidden_layers"], cfg["vocab_size"], d // h
    norm = {"scale": (n, d), "bias": (n, d)}
    return {
        "embed": (v, d),
        "blocks": {"layer0": {
            "norm1": norm, "norm2": norm,
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, hkv, hd),
                     "wv": (n, d, hkv, hd), "wo": (n, h, hd, d)},
            "mlp": {"wi": (n, d, f), "wg": (n, d, f), "wo": (n, f, d)}}},
        "final_norm": {"scale": (d,), "bias": (d,)},
        "lm_head": (d, v),
    }


def _leaf(key, path: str, shape: tuple):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    # fan-in: every axis but the last (per layer), or d for the embedding
    if path == "embed":
        fan_in = shape[1]
    elif path.endswith("attn/wo"):
        fan_in = shape[-3] * shape[-2]
    else:
        fan_in = shape[-3] if len(shape) == 4 else shape[-2]
    w = jax.random.normal(key, shape, jnp.bfloat16)
    return w * jnp.asarray(fan_in ** -0.5, jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, frozen: tuple):
    out = {}
    for i, (path, shape) in enumerate(frozen):
        out[path] = _leaf(jax.random.fold_in(key, i), path, shape)
    return out


def _flatten(tree, prefix=""):
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _flatten(v, path)
        else:
            yield path, tuple(v)


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
