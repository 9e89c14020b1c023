"""Unified model API dispatching decoder-only vs encoder-decoder archs.

Batch conventions (match launch.input_specs):
  * decoder-only, frontend=tokens:       {"tokens": (B, S) int32}
  * decoder-only, frontend=embeddings:   {"embeddings": (B, S, d)}
  * encoder-decoder (whisper):           {"frames": (B, S, d),
                                          "tokens": (B, T) int32}
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import encdec, transformer

PyTree = Any


def init_params(key, cfg: ArchConfig) -> PyTree:
    if cfg.is_encoder_decoder:
        return encdec.init_params(key, cfg)
    return transformer.init_params(key, cfg)


def forward(params: PyTree, cfg: ArchConfig, batch: dict):
    """Training forward -> (fp32 logits, aux loss)."""
    if cfg.is_encoder_decoder:
        return encdec.forward(params, cfg, batch["frames"], batch["tokens"])
    inp = batch.get("tokens", batch.get("embeddings"))
    return transformer.forward(params, cfg, inp)


def prefill(params: PyTree, cfg: ArchConfig, batch: dict, *,
            moe_dropless: bool = True):
    """-> (last-token fp32 logits (B, V), cache). ``moe_dropless``: see
    ``transformer.prefill``."""
    if cfg.is_encoder_decoder:
        return encdec.prefill(params, cfg, batch["frames"], batch["tokens"])
    inp = batch.get("tokens", batch.get("embeddings"))
    return transformer.prefill(params, cfg, inp, moe_dropless=moe_dropless)


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    if cfg.is_encoder_decoder:
        return encdec.init_cache(cfg, batch, enc_len=max_len)
    return transformer.init_cache(cfg, batch, max_len)


def init_moe_counts(cfg: ArchConfig):
    """Zeroed decode-step MoE counters, None without experts (see
    ``transformer.init_moe_counts``)."""
    if cfg.is_encoder_decoder:
        return None
    return transformer.init_moe_counts(cfg)


def decode_step(params: PyTree, cfg: ArchConfig, tokens: jax.Array,
                cache: PyTree, pos: jax.Array, *, moe_dropless: bool = True,
                moe_counts: Optional[jax.Array] = None):
    """-> ((B, V) fp32 logits, new cache), and the MoE counters when
    given ``moe_counts``; see ``transformer.decode_step``."""
    if cfg.is_encoder_decoder:
        return encdec.decode_step(params, cfg, tokens, cache, pos)
    return transformer.decode_step(params, cfg, tokens, cache, pos,
                                   moe_dropless=moe_dropless,
                                   moe_counts=moe_counts)


# ------------------------------------------------------------- accounting
def param_shapes(cfg: ArchConfig) -> PyTree:
    """Exact parameter shapes via eval_shape — no allocation."""
    return jax.eval_shape(
        lambda k: init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def param_count(cfg: ArchConfig) -> int:
    import math
    shapes = param_shapes(cfg)
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


def active_param_count(cfg: ArchConfig) -> int:
    """Params touched per token: total minus the (n_experts - top_k)
    unused expert slices per MoE layer."""
    total = param_count(cfg)
    if cfg.n_experts == 0:
        return total
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    per_expert = cfg.d_model * cfg.d_ff * (3 if gated else 2)
    inactive = cfg.n_layers * (cfg.n_experts - cfg.top_k) * per_expert
    return total - inactive
