"""Whisper-style encoder-decoder (audio arch). arXiv:2212.04356.

The mel-spectrogram + conv feature extractor is the stubbed modality
frontend: the encoder consumes precomputed frame embeddings
(B, S_audio, d_model) from ``input_specs`` and adds sinusoidal positions.
Everything downstream — bidirectional encoder, causal decoder with
cross-attention, prefill/decode with self-KV + precomputed cross-KV —
is implemented in full.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers

PyTree = Any


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _spec(cfg: ArchConfig, causal: bool) -> layers.AttnSpec:
    return layers.AttnSpec(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, causal=causal, use_rope=False,
        softcap=cfg.attn_softcap)


def _enc_layer_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": layers.norm_init(cfg.norm, cfg.d_model),
        "attn": layers.attention_init(k1, _spec(cfg, causal=False), _dtype(cfg)),
        "norm2": layers.norm_init(cfg.norm, cfg.d_model),
        "mlp": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                               _dtype(cfg)),
    }


def _dec_layer_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": layers.norm_init(cfg.norm, cfg.d_model),
        "self_attn": layers.attention_init(k1, _spec(cfg, causal=True),
                                           _dtype(cfg)),
        "norm_x": layers.norm_init(cfg.norm, cfg.d_model),
        "cross_attn": layers.cross_attention_init(k2, _spec(cfg, causal=False),
                                                  _dtype(cfg)),
        "norm2": layers.norm_init(cfg.norm, cfg.d_model),
        "mlp": layers.mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                               _dtype(cfg)),
    }


def init_params(key, cfg: ArchConfig) -> PyTree:
    ke, kd, kemb, kh = jax.random.split(key, 4)
    enc_keys = jax.random.split(ke, cfg.n_encoder_layers)
    dec_keys = jax.random.split(kd, cfg.n_layers)
    enc = [_enc_layer_init(k, cfg) for k in enc_keys]
    dec = [_dec_layer_init(k, cfg) for k in dec_keys]
    return {
        "embed": (jax.random.normal(kemb, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32)
                  * cfg.d_model ** -0.5).astype(_dtype(cfg)),
        "enc_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
        "enc_norm": layers.norm_init(cfg.norm, cfg.d_model),
        "dec_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *dec),
        "final_norm": layers.norm_init(cfg.norm, cfg.d_model),
        "lm_head": layers._dense_init(kh, (cfg.d_model, cfg.vocab_size),
                                      cfg.d_model, _dtype(cfg)),
    }


# ---------------------------------------------------------------- encoder
def encode(params: PyTree, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, S, d) stubbed conv-frontend output -> encoder states."""
    b, s, d = frames.shape
    x = frames.astype(_dtype(cfg)) + \
        layers.sinusoidal_positions(s, d)[None].astype(_dtype(cfg))
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    spec = _spec(cfg, causal=False)

    def body(x, p):
        h = layers.apply_norm(cfg.norm, p["norm1"], x)
        x = x + layers.self_attention(p["attn"], spec, h, positions)
        h = layers.apply_norm(cfg.norm, p["norm2"], x)
        x = x + layers.mlp(p["mlp"], h, cfg.mlp_kind)
        return x, None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(fn, x, params["enc_blocks"])
    return layers.apply_norm(cfg.norm, params["enc_norm"], x)


# ---------------------------------------------------------------- decoder
def _dec_layer(p, cfg, x, positions, enc_k, enc_v):
    spec = _spec(cfg, causal=True)
    h = layers.apply_norm(cfg.norm, p["norm1"], x)
    x = x + layers.self_attention(p["self_attn"], spec, h, positions)
    h = layers.apply_norm(cfg.norm, p["norm_x"], x)
    x = x + layers.cross_attention(p["cross_attn"], _spec(cfg, False), h,
                                   enc_k, enc_v)
    h = layers.apply_norm(cfg.norm, p["norm2"], x)
    return x + layers.mlp(p["mlp"], h, cfg.mlp_kind)


def forward(params: PyTree, cfg: ArchConfig, frames: jax.Array,
            tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Training forward: (frames, decoder tokens) -> fp32 logits, aux=0."""
    enc_out = encode(params, cfg, frames)
    b, t = tokens.shape
    d = cfg.d_model
    x = params["embed"][tokens] + \
        layers.sinusoidal_positions(t, d)[None].astype(_dtype(cfg))
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    def body(x, p):
        k, v = layers.cross_kv(p["cross_attn"], _spec(cfg, False), enc_out)
        return _dec_layer(p, cfg, x, positions, k, v), None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(fn, x, params["dec_blocks"])
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    logits = jax.lax.dot_general(x, params["lm_head"],
                                 (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return logits, jnp.zeros((), jnp.float32)


# --------------------------------------------------------------- serving
def init_cache(cfg: ArchConfig, batch: int, enc_len: int) -> PyTree:
    dt = _dtype(cfg)
    L, T = cfg.n_layers, cfg.max_decoder_len
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    w = layers.kv_row_width(hd)
    return {
        "self_k": jnp.zeros((L, batch, T, hkv, w), dt),
        "self_v": jnp.zeros((L, batch, T, hkv, w), dt),
        "self_pos": jnp.full((L, batch, T), -1, jnp.int32),
        "cross_k": jnp.zeros((L, batch, enc_len, hkv, hd), dt),
        "cross_v": jnp.zeros((L, batch, enc_len, hkv, hd), dt),
    }


def prefill(params: PyTree, cfg: ArchConfig, frames: jax.Array,
            tokens: jax.Array) -> tuple[jax.Array, PyTree]:
    """Encode frames, precompute cross-KV, prefill decoder self-KV.
    Returns (last-token fp32 logits, cache)."""
    enc_out = encode(params, cfg, frames)
    b, t = tokens.shape
    x = params["embed"][tokens] + layers.sinusoidal_positions(
        t, cfg.d_model)[None].astype(_dtype(cfg))
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    spec = _spec(cfg, causal=True)

    def body(x, p):
        ck, cv = layers.cross_kv(p["cross_attn"], _spec(cfg, False), enc_out)
        h = layers.apply_norm(cfg.norm, p["norm1"], x)
        y, kv = layers.self_attention_prefill(p["self_attn"], spec, h,
                                              positions, cfg.max_decoder_len)
        x = x + y
        h = layers.apply_norm(cfg.norm, p["norm_x"], x)
        x = x + layers.cross_attention(p["cross_attn"], _spec(cfg, False), h,
                                       ck, cv)
        h = layers.apply_norm(cfg.norm, p["norm2"], x)
        x = x + layers.mlp(p["mlp"], h, cfg.mlp_kind)
        return x, {"self_k": kv["k"], "self_v": kv["v"], "self_pos": kv["pos"],
                   "cross_k": ck, "cross_v": cv}

    x, cache = jax.lax.scan(body, x, params["dec_blocks"])
    x = layers.apply_norm(cfg.norm, params["final_norm"], x[:, -1:, :])
    logits = jax.lax.dot_general(x, params["lm_head"],
                                 (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return logits[:, 0, :], cache


def decode_step(params: PyTree, cfg: ArchConfig, tokens: jax.Array,
                cache: PyTree, pos: jax.Array) -> tuple[jax.Array, PyTree]:
    """One decoder token against self-KV + cross-KV caches."""
    spec = _spec(cfg, causal=True)
    x = params["embed"][tokens][:, None, :] + \
        layers.sinusoidal_positions(int(cfg.max_decoder_len),
                                    cfg.d_model)[None, :1].astype(_dtype(cfg))

    def body(carry, scanned):
        # cache in the CARRY with in-place per-layer updates (see
        # transformer.decode_step for the aliasing rationale)
        x, cache_all = carry
        p, i = scanned
        c = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            cache_all)
        h = layers.apply_norm(cfg.norm, p["norm1"], x)
        kv = {"k": c["self_k"], "v": c["self_v"], "pos": c["self_pos"]}
        y, kv = layers.self_attention_decode(p["self_attn"], spec, h, kv, pos)
        x = x + y
        h = layers.apply_norm(cfg.norm, p["norm_x"], x)
        x = x + layers.cross_attention(p["cross_attn"], _spec(cfg, False), h,
                                       c["cross_k"], c["cross_v"])
        h = layers.apply_norm(cfg.norm, p["norm2"], x)
        x = x + layers.mlp(p["mlp"], h, cfg.mlp_kind)
        upd = {"self_k": kv["k"], "self_v": kv["v"], "self_pos": kv["pos"]}
        for key in upd:
            cache_all = dict(cache_all)
            cache_all[key] = jax.lax.dynamic_update_index_in_dim(
                cache_all[key], upd[key], i, 0)
        return (x, cache_all), None

    (x, new_cache), _ = jax.lax.scan(
        body, (x, cache),
        (params["dec_blocks"], jnp.arange(cfg.n_layers)))
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    logits = jax.lax.dot_general(x, params["lm_head"],
                                 (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return logits[:, 0, :], new_cache
