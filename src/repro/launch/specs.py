"""ShapeDtypeStruct stand-ins for every (arch x shape) input — weak-type
correct, shardable, zero allocation. Consumed by launch/dryrun.py."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, InputShape
from repro.models import model
from repro.training import optimizer as opt
from repro.training.train import make_functional_step

PyTree = Any


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _tree_sds(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda l: sds(l.shape, l.dtype), tree)


def train_batch_specs(cfg: ArchConfig, shape: InputShape) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if cfg.is_encoder_decoder:
        t = cfg.max_decoder_len
        return {"frames": sds((b, s, cfg.d_model), cfg.dtype),
                "tokens": sds((b, t), jnp.int32),
                "labels": sds((b, t), jnp.int32)}
    if cfg.frontend == "embeddings":
        return {"embeddings": sds((b, s, cfg.d_model), cfg.dtype),
                "labels": sds((b, s), jnp.int32)}
    return {"tokens": sds((b, s), jnp.int32),
            "labels": sds((b, s), jnp.int32)}


def prefill_batch_specs(cfg: ArchConfig, shape: InputShape) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if cfg.is_encoder_decoder:
        return {"frames": sds((b, s, cfg.d_model), cfg.dtype),
                "tokens": sds((b, cfg.max_decoder_len), jnp.int32)}
    if cfg.frontend == "embeddings":
        return {"embeddings": sds((b, s, cfg.d_model), cfg.dtype)}
    return {"tokens": sds((b, s), jnp.int32)}


def params_specs(cfg: ArchConfig) -> PyTree:
    return model.param_shapes(cfg)


def opt_state_specs(cfg: ArchConfig) -> PyTree:
    pshapes = params_specs(cfg)
    ocfg = opt.AdamWConfig(state_dtype=cfg.opt_state_dtype)
    return jax.eval_shape(lambda p: opt.init_opt_state(p, ocfg), pshapes)


def cache_specs(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    return jax.eval_shape(
        lambda: model.init_cache(cfg, batch, max_len))


def decode_token_specs(cfg: ArchConfig, shape: InputShape):
    b = shape.global_batch
    tokens = sds((b,), jnp.int32)
    pos = sds((b,), jnp.int32)
    return tokens, pos


def step_fn_for(cfg: ArchConfig, shape: InputShape):
    """The pure function the dry-run lowers, plus its input spec tuple.

    Returns (fn, arg_specs: tuple) with fn signature matching arg_specs.
    """
    if shape.kind == "train":
        ocfg = opt.AdamWConfig(state_dtype=cfg.opt_state_dtype)
        fn = make_functional_step(cfg, ocfg)
        args = (params_specs(cfg), opt_state_specs(cfg),
                train_batch_specs(cfg, shape))
        return fn, args
    # the serving shapes lower the capacity MoE: GSPMD shards its expert
    # einsum over the model axis, and a Mosaic kernel (the dropless
    # path's moe_gmm) cannot be partitioned
    if shape.kind == "prefill":
        fn = lambda params, batch: model.prefill(params, cfg, batch,
                                                 moe_dropless=False)
        return fn, (params_specs(cfg), prefill_batch_specs(cfg, shape))
    # decode: one new token against a seq_len-deep cache
    fn = lambda params, tokens, cache, pos: model.decode_step(
        params, cfg, tokens, cache, pos, moe_dropless=False)
    tokens, pos = decode_token_specs(cfg, shape)
    cache = cache_specs(cfg, shape.global_batch, shape.seq_len)
    return fn, (params_specs(cfg), tokens, cache, pos)
