"""Plain float32 reference of a Mellum2 decoder (the architecture of
JetBrains/Mellum2-12B-A2.5B-Instruct), written from its published
config.json: pre-norm blocks with RMSNorm (``(1 + scale)`` form, see
the configuration's ``assumed``), grouped-query attention with rotary
embedding (rotate-half convention) and a causal mask, narrowed on the
``sliding_attention`` layers to the last ``sliding_window`` positions
(a key k is visible from query q iff q - window < k <= q); the rotary
frequencies of each layer type come from its ``rope_parameters``, plain
or YaRN (frequencies blended over a ramp between correction dims, cos
and sin scaled by ``attention_factor``). Every MLP is a dropless top-k
mixture of SwiGLU experts: a softmax router, the top ``k`` experts,
their gates renormalised; each expert computes its own rows in blocks.
A final RMSNorm and an untied head. Every matmul runs at ``highest``
precision, under ``jax.default_matmul_precision("highest")``. It
imports nothing of the program; the weights come from
``bench/weights_moe.py`` and the seed.

To fit 16 requests of ~2k tokens beside the weights on one chip,
attention runs one request at a time and each expert its routed rows
in blocks of ``BLOCK`` rows.

``served_gaps`` answers the question the served cell asks: at each
position where the program served a token, by how much does that
token's logit lie below the reference's best?

``int8_gaps`` is the control, one precision below what the
configuration states: every bf16 weight matmul with both operands on
symmetric int8 levels (weights per output column, activations per
token), the f32 router's operands rounded to bf16; at each position,
the gap of the token that it puts first. ``int8_next`` is the same
control decoding: the token it puts first after each sequence.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.stablelm import _mm_f32, _mm_int8, _sequence
from bench.weights_moe import pattern

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512           # expert rows computed at a time


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the forward pass reads of a configuration, hashable."""
    d: int
    h: int
    hkv: int
    hd: int
    experts: int
    top_k: int
    eps: float
    window: int
    kinds: tuple              # the repeating run of layer types
    rope: tuple               # per kind: sorted (key, value) items

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        kinds = pattern(cfg)
        return cls(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                   hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                   experts=cfg["num_experts"],
                   top_k=cfg["num_experts_per_tok"],
                   eps=float(cfg["rms_norm_eps"]),
                   window=cfg["sliding_window"], kinds=kinds,
                   rope=tuple(tuple(sorted(cfg["rope_parameters"][k].items()))
                              for k in kinds))


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _inv_freq(hd: int, rope: dict):
    """(hd/2,) inverse frequencies and the cos/sin scale of one layer
    type, from its ``rope_parameters``."""
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    if rope["rope_type"] == "default":
        return inv, 1.0
    assert rope["rope_type"] == "yarn", rope
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return hd * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    inv = (inv / factor) * (1.0 - extrapolate) + inv * extrapolate
    return inv, float(rope["attention_factor"])


def _rope(x, inv, mscale):
    """Rotate-half rotary embedding of one request's x (S, heads, hd) at
    positions 0..S-1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]          # (S, 1, hd)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * (jnp.cos(emb) * mscale) + rotated * (jnp.sin(emb) * mscale)


def _attend(q, k, v, window: int):
    """One request: q (S, H, hd), k/v (S, Hkv, hd) -> (S, H, hd)."""
    s = q.shape[0]
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhk,shk->hqs", q, k, precision=HIGHEST) \
        * q.shape[-1] ** -0.5
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    visible = ki <= qi
    if window:
        visible &= ki > qi - window
    pr = jax.nn.softmax(jnp.where(visible[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqs,shk->qhk", pr, v, precision=HIGHEST)


def _router(x, w, mm):
    if mm is _mm_f32:
        return _mm_f32(x, w, "td,de->te")
    # the control: the f32 router one precision below, bf16 operands
    return jnp.einsum("td,de->te",
                      x.astype(jnp.bfloat16).astype(jnp.float32),
                      w.astype(jnp.bfloat16).astype(jnp.float32),
                      precision=HIGHEST)


def _moe(x, p, shp: Shape, mm):
    """Dropless top-k SwiGLU experts over the tokens x (T, d)."""
    t = x.shape[0]
    k = shp.top_k
    probs = jax.nn.softmax(_router(x, p["router"], mm), axis=-1)
    gate, expert = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    order = jnp.argsort(expert.reshape(-1), stable=True)
    sizes = jnp.bincount(expert.reshape(-1), length=shp.experts)
    starts = jnp.cumsum(sizes) - sizes
    pad = jnp.zeros((BLOCK,), jnp.int32)
    token = jnp.concatenate([(order // k).astype(jnp.int32), pad])
    weight = jnp.concatenate([gate.reshape(-1)[order],
                              pad.astype(jnp.float32)])

    def expert_rows(e, out):
        def block(j, out):
            at = starts[e] + j * BLOCK
            idx = jax.lax.dynamic_slice(token, (at,), (BLOCK,))
            g = jax.lax.dynamic_slice(weight, (at,), (BLOCK,))
            g = jnp.where(j * BLOCK + jnp.arange(BLOCK) < sizes[e], g, 0.0)
            xb = x[idx]
            hid = jax.nn.silu(mm(xb, p["wg"][e], "rd,df->rf")) \
                * mm(xb, p["wi"][e], "rd,df->rf")
            return out.at[idx].add(mm(hid, p["wo"][e], "rf,fd->rd")
                                   * g[:, None])
        return jax.lax.fori_loop(0, (sizes[e] + BLOCK - 1) // BLOCK, block,
                                 out)

    return jax.lax.fori_loop(0, shp.experts, expert_rows,
                             jnp.zeros((t, x.shape[1]), jnp.float32))


def _forward(params, tokens, shp: Shape, mm):
    """Hidden states (B, S, d) in float32 after the final norm."""
    b, s = tokens.shape
    ropes = [_inv_freq(shp.hd, dict(r)) for r in shp.rope]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p, kind, rope):
        window = shp.window if kind == "sliding_attention" else 0

        def attention(xr):                     # one request, (S, d)
            a = _rmsnorm(xr, p["norm1"]["scale"], shp.eps)
            q = _rope(mm(a, p["attn"]["wq"], "sd,dhk->shk"), *rope)
            k = _rope(mm(a, p["attn"]["wk"], "sd,dhk->shk"), *rope)
            v = mm(a, p["attn"]["wv"], "sd,dhk->shk")
            o = _attend(q, k, v, window)
            return xr + mm(o, p["attn"]["wo"], "shk,hkd->sd")

        x = jax.lax.map(attention, x)
        m = _rmsnorm(x, p["norm2"]["scale"], shp.eps)
        y = _moe(m.reshape(b * s, shp.d), p["moe"], shp, mm)
        return x + y.reshape(b, s, shp.d)

    def period(x, block):
        for j, kind in enumerate(shp.kinds):
            x = layer(x, block[f"layer{j}"], kind, ropes[j])
        return x, None

    x, _ = jax.lax.scan(period, x, params["blocks"])
    return _rmsnorm(x, params["final_norm"]["scale"], shp.eps)


def _logits(hid, params, mm):
    """(B, T, V) logits of hidden states, one request at a time."""
    return jax.lax.map(lambda h: mm(h, params["lm_head"], "td,dv->tv"), hid)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(params, tokens, served, n_prompt, shp):
    hid = _forward(params, tokens, shp, _mm_f32)[:, n_prompt - 1:]

    def one(args):
        h, srv = args
        logits = _mm_f32(h, params["lm_head"], "td,dv->tv")
        got = jnp.take_along_axis(logits, srv[:, None], -1)[:, 0]
        return jnp.max(logits, -1) - got
    return jax.lax.map(one, (hid, served))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _int8_gaps(params, tokens, n_prompt, shp):
    hid = _forward(params, tokens, shp, _mm_f32)[:, n_prompt - 1:]
    hid8 = _forward(params, tokens, shp, _mm_int8)[:, n_prompt - 1:]

    def one(args):
        h, h8 = args
        logits = _mm_f32(h, params["lm_head"], "td,dv->tv")
        pick = jnp.argmax(_mm_int8(h8, params["lm_head"], "td,dv->tv"), -1)
        got = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
        return jnp.max(logits, -1) - got
    return jax.lax.map(one, (hid, hid8))


@functools.partial(jax.jit, static_argnums=(2,))
def _int8_next(params, tokens, shp):
    hid8 = _forward(params, tokens, shp, _mm_int8)[:, -1:]
    return jnp.argmax(_logits(hid8, params, _mm_int8)[:, 0], -1)


def served_gaps(params, cfg: dict, prompts, served):
    """(B, T) logit gaps of the T served tokens of each request."""
    with jax.default_matmul_precision("highest"):
        return _gaps(params, _sequence(prompts, served), jnp.asarray(served),
                     prompts.shape[1], Shape.of(cfg))


def int8_gaps(params, cfg: dict, prompts, served):
    """(B, T) gaps of the tokens the int8 control puts first, at the
    positions of the served tokens."""
    with jax.default_matmul_precision("highest"):
        return _int8_gaps(params, _sequence(prompts, served),
                          prompts.shape[1], Shape.of(cfg))


def int8_next(params, cfg: dict, tokens):
    """(B,) the token the int8 control puts first after each sequence."""
    with jax.default_matmul_precision("highest"):
        return _int8_next(params, jnp.asarray(tokens), Shape.of(cfg))


def logits(params, cfg: dict, tokens):
    """(B, S, V) float32 logits at every position (the tests' check)."""
    with jax.default_matmul_precision("highest"):
        return _all_logits(params, jnp.asarray(tokens), Shape.of(cfg))


@functools.partial(jax.jit, static_argnums=(2,))
def _all_logits(params, tokens, shp):
    return _logits(_forward(params, tokens, shp, _mm_f32), params, _mm_f32)
