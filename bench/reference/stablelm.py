"""Plain float32 reference of a StableLM decoder (the architecture of
stabilityai/stablelm-3b-4e1t), written from its published description:
pre-norm blocks with LayerNorm (scale and bias), multi-head attention
with rotary embedding on the first ``partial_rotary_factor`` of each
head (rotate-half convention; the configuration's ``assumed`` block
gives the fraction that runs), a causal mask, a SiLU-gated MLP
``wo(silu(x wg) * (x wi))``, a final LayerNorm and an untied head.
Every matmul runs at ``highest`` precision. It imports nothing of the
program; the weights come from ``bench/weights.py`` and the seed.

``served_gaps`` answers the question the served cell asks: at each
position where the program served a token, by how much does that
token's logit lie below the reference's best?

``int8_gaps`` is the control, one precision below the bf16 the
configuration states: the same network with both operands of every
weight matmul on symmetric int8 levels (weights per output column,
activations per token), and, at each position, the gap of the token
that it puts first. ``int8_next`` is the same control decoding: the
token it puts first after each sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _layernorm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta, fraction):
    hd = x.shape[-1]
    rot = int(hd * fraction)
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = pos[:, :, None, None].astype(jnp.float32) * inv      # (B,S,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _mm_f32(x, w, spec):
    return jnp.einsum(spec, x, w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fake_int8(x, axes):
    """x rounded to int8 levels of a symmetric scale per slice, the scale
    taken over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-30)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm_int8(x, w, spec):
    """W8A8: both operands on int8 levels, each scaled per slice over the
    axes the product contracts (per token for x, per output column for
    w), then multiplied exactly in float32."""
    ins, out = spec.split("->")
    sx, sw = ins.split(",")
    contracted = set(sx) & set(sw) - set(out)
    ax = tuple(i for i, c in enumerate(sx) if c in contracted)
    aw = tuple(i for i, c in enumerate(sw) if c in contracted)
    return jnp.einsum(spec, _fake_int8(x, ax),
                      _fake_int8(w.astype(jnp.float32), aw),
                      precision=jax.lax.Precision.HIGHEST)


def _forward(params, tokens, cfg, mm):
    """Hidden states (B, S, d) in float32 after the final norm."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = d // h
    eps = cfg["layer_norm_eps"]
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = jnp.tril(jnp.ones((s, s), bool))
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = _layernorm(x, p["norm1"], eps)
        q = mm(a, p["attn"]["wq"], "bsd,dhk->bshk")
        k = mm(a, p["attn"]["wk"], "bsd,dhk->bshk")
        v = mm(a, p["attn"]["wv"], "bsd,dhk->bshk")
        q = _rope(q, pos, cfg["rope_theta"], cfg["partial_rotary_factor"])
        k = _rope(k, pos, cfg["rope_theta"], cfg["partial_rotary_factor"])
        if hkv != h:
            k = jnp.repeat(k, h // hkv, axis=2)
            v = jnp.repeat(v, h // hkv, axis=2)
        sc = jnp.einsum("bqhk,bshk->bhqs", q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqs,bshk->bqhk", pr, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + mm(o, p["attn"]["wo"], "bshk,hkd->bsd")
        m = _layernorm(x, p["norm2"], eps)
        gate = jax.nn.silu(mm(m, p["mlp"]["wg"], "bsd,df->bsf"))
        up = mm(m, p["mlp"]["wi"], "bsd,df->bsf")
        x = x + mm(gate * up, p["mlp"]["wo"], "bsf,fd->bsd")
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["layer0"])
    return _layernorm(x, params["final_norm"], eps)


def _frozen(cfg: dict) -> tuple:
    """The configuration's numbers, with what it assumes (``assumed``)
    in place of what it publishes."""
    flat = {**cfg, **cfg.get("assumed", {})}
    return tuple(sorted((k, v) for k, v in flat.items()
                        if isinstance(v, (int, float, str))))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(params, tokens, served, n_prompt, cfg_items):
    cfg = dict(cfg_items)
    hid = _forward(params, tokens, cfg, _mm_f32)[:, n_prompt - 1:]
    logits = _mm_f32(hid, params["lm_head"], "bsd,dv->bsv")
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    return best - got


@functools.partial(jax.jit, static_argnums=(2, 3))
def _int8_gaps(params, tokens, n_prompt, cfg_items):
    cfg = dict(cfg_items)
    hid = _forward(params, tokens, cfg, _mm_f32)[:, n_prompt - 1:]
    logits = _mm_f32(hid, params["lm_head"], "bsd,dv->bsv")
    hid8 = _forward(params, tokens, cfg, _mm_int8)[:, n_prompt - 1:]
    logits8 = _mm_int8(hid8, params["lm_head"], "bsd,dv->bsv")
    pick = jnp.argmax(logits8, -1)
    got = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
    return jnp.max(logits, -1) - got


def _sequence(prompts, served):
    """Prompt plus every served token but the last: the positions whose
    logits chose the served tokens are n_prompt - 1 .. end."""
    return jnp.concatenate([jnp.asarray(prompts),
                            jnp.asarray(served)[:, :-1]], axis=1)


def served_gaps(params, cfg: dict, prompts, served):
    """(B, T) logit gaps of the T served tokens of each request."""
    return _gaps(params, _sequence(prompts, served), jnp.asarray(served),
                 prompts.shape[1], _frozen(cfg))


def int8_gaps(params, cfg: dict, prompts, served):
    """(B, T) gaps of the tokens the int8 control puts first, at the
    positions of the served tokens."""
    return _int8_gaps(params, _sequence(prompts, served), prompts.shape[1],
                      _frozen(cfg))


@functools.partial(jax.jit, static_argnums=(2,))
def _int8_next(params, tokens, cfg_items):
    cfg = dict(cfg_items)
    hid8 = _forward(params, tokens, cfg, _mm_int8)[:, -1]
    return jnp.argmax(_mm_int8(hid8, params["lm_head"], "bd,dv->bv"), -1)


def int8_next(params, cfg: dict, tokens):
    """(B,) the token the int8 control puts first after each sequence."""
    return _int8_next(params, jnp.asarray(tokens), _frozen(cfg))
