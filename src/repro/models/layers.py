"""Shared transformer layer primitives (pure-function style, params as
pytrees of jnp arrays). Every assigned architecture is assembled from
these in ``repro.models.transformer`` / ``encdec``.

Design notes
------------
* No flax/haiku: params are plain nested dicts, init functions return
  them, apply functions take them. This keeps sharding rules (path ->
  PartitionSpec) and scan-over-layers stacking trivial.
* Attention math is delegated to ``repro.kernels.ops`` which dispatches
  between the pure-jnp oracle (CPU, dry-run) and the Pallas TPU kernels.
* All matmuls accumulate in float32 (preferred_element_type) and cast
  back to the activation dtype.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed import sharding as dist
from repro.kernels import ops


def _dense_init(key, shape, in_axis_size, dtype):
    scale = in_axis_size ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w with fp32 accumulation, output in x.dtype."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


# ------------------------------------------------------------------ norms
def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + params["scale"])
    return y.astype(x.dtype)


def layernorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def norm_init(kind: str, d: int) -> dict:
    return rmsnorm_init(d) if kind == "rmsnorm" else layernorm_init(d)


def apply_norm(kind: str, params: dict, x: jax.Array) -> jax.Array:
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


# ------------------------------------------------------------------ rope
def rope_frequencies(d: int, theta: float, yarn=None):
    """(d/2,) rotation frequencies theta^(-2i/d) and the factor cos and
    sin are scaled by. With ``yarn`` (``configs.base.Yarn``) each
    frequency is blended with itself over ``yarn.factor``: the plain one
    below the correction dim of ``beta_fast`` rotations, the divided one
    above that of ``beta_slow``, a linear ramp between (transformers'
    ``_compute_yarn_parameters``, ``truncate`` on)."""
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if yarn is None:
        return freq, 1.0

    def dim_of(rotations):
        return (d * math.log(yarn.original_max_positions
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    freq = freq / yarn.factor * ramp + freq * (1.0 - ramp)
    return freq, yarn.attention_factor


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         yarn=None) -> jax.Array:
    """Rotary embedding. x: (..., S, H, D), positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq, mscale = rope_frequencies(d, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freq       # (..., S, half)
    angles = angles[..., None, :]                                  # (..., S, 1, half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin
    y2 = x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def sinusoidal_positions(length: int, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal position embeddings (length, d)."""
    half = d // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / (half - 1))
    pos = jnp.arange(length, dtype=jnp.float32)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(pos), jnp.cos(pos)], axis=1)


# ------------------------------------------------------------- attention
@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: int = 0          # 0 = global
    softcap: float = 0.0
    causal: bool = True
    use_rope: bool = True
    qk_norm: bool = False    # chameleon-style query/key RMSNorm
    scale: Optional[float] = None
    yarn: Any = None         # configs.base.Yarn: YaRN-scaled rotary


def attention_init(key, spec: AttnSpec, dtype) -> dict:
    """Weights are stored head-separated — wq: (d, H, hd), wo: (H, hd, d)
    — so tensor-parallel sharding of the head axis is a plain
    PartitionSpec with no post-matmul reshape resharding."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, hkv, hd = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    p = {
        "wq": _dense_init(kq, (d, h, hd), d, dtype),
        "wk": _dense_init(kk, (d, hkv, hd), d, dtype),
        "wv": _dense_init(kv, (d, hkv, hd), d, dtype),
        "wo": _dense_init(ko, (h, hd, d), h * hd, dtype),
    }
    if spec.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def _proj_heads(x: jax.Array, w: jax.Array) -> jax.Array:
    """(..., d) @ (d, H, hd) -> (..., H, hd), fp32 accumulation."""
    return jnp.einsum("...d,dhk->...hk", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _proj_out(x: jax.Array, w: jax.Array) -> jax.Array:
    """(..., H, hd) @ (H, hd, d) -> (..., d), fp32 accumulation."""
    return jnp.einsum("...hk,hkd->...d", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _project_qkv(params, spec: AttnSpec, x, positions):
    q = _proj_heads(x, params["wq"])
    k = _proj_heads(x, params["wk"])
    v = _proj_heads(x, params["wv"])
    if spec.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if spec.use_rope:
        q = rope(q, positions, spec.rope_theta, spec.yarn)
        k = rope(k, positions, spec.rope_theta, spec.yarn)
    return q, k, v


def self_attention(params: dict, spec: AttnSpec, x: jax.Array,
                   positions: jax.Array) -> jax.Array:
    """Training/prefill self-attention over a full sequence."""
    q, k, v = _project_qkv(params, spec, x, positions)
    out = ops.attention(q, k, v, causal=spec.causal, window=spec.window,
                        softcap=spec.softcap, scale=spec.scale,
                        segment_pos=positions)
    return _proj_out(out, params["wo"])


def kv_row_width(head_dim: int) -> int:
    """Lanes one head's row takes in a KV cache: ``head_dim`` rounded up
    to whole 128-lane tiles, the pad zero and never attended.

    The decode-attention kernel reads K/V row-major, and a TPU lays a
    (..., C, Hkv, D) array out row-major only when D fills whole lane
    tiles; for any other D it puts C minor-most, so a decode step would
    relay the whole cache into the kernel's layout and back on every
    step. Padded rows take the HBM a row-major unpadded cache takes."""
    return -(-head_dim // 128) * 128


def _pad_row(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last (head_dim) axis of ``x`` to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def self_attention_prefill(params: dict, spec: AttnSpec, x: jax.Array,
                           positions: jax.Array, cache_len: int):
    """Prefill: full attention + return the KV cache (ring-buffered to
    cache_len slots, newest tokens win). ``positions`` rise by one along
    the sequence, as prefill's do."""
    q, k, v = _project_qkv(params, spec, x, positions)
    out = ops.attention(q, k, v, causal=spec.causal, window=spec.window,
                        softcap=spec.softcap, scale=spec.scale,
                        segment_pos=positions)
    b, s = out.shape[:2]
    y = _proj_out(out, params["wo"])

    # scatter the last cache_len tokens into ring slots pos % cache_len:
    # only they survive, and each takes a slot of its own (a scatter with
    # two writers to one slot keeps either on a TPU)
    if s > cache_len:
        k, v, positions = (a[:, s - cache_len:] for a in (k, v, positions))
    slots = positions % cache_len
    width = kv_row_width(spec.head_dim)
    k_cache = jnp.zeros((b, cache_len, spec.n_kv_heads, width), k.dtype)
    v_cache = jnp.zeros_like(k_cache)
    kv_pos = jnp.full((b, cache_len), -1, jnp.int32)
    bidx = jnp.arange(b)[:, None]
    k_cache = k_cache.at[bidx, slots].set(_pad_row(k, width))
    v_cache = v_cache.at[bidx, slots].set(_pad_row(v, width))
    kv_pos = kv_pos.at[bidx, slots].set(positions.astype(jnp.int32))
    return y, {"k": k_cache, "v": v_cache, "pos": kv_pos}


def self_attention_decode(params: dict, spec: AttnSpec, x: jax.Array,
                          cache: dict, q_pos: jax.Array,
                          layer: Optional[jax.Array] = None):
    """One-token decode. x: (B, 1, d); q_pos: (B,) absolute position.

    ``cache`` holds one layer, k/v (B, C, Hkv, W) and pos (B, C); or,
    with ``layer`` (an int32 scalar), the layer-stacked k/v
    (L, B, C, Hkv, W) and pos (L, B, C), of which layer ``layer`` is
    written and attended in place. W >= head_dim is the cache's row
    width (``kv_row_width``)."""
    b = x.shape[0]
    q, k, v = _project_qkv(params, spec, x, q_pos[:, None])
    cache_len = cache["pos"].shape[-1]
    width = cache["k"].shape[-1]
    slot = (q_pos % cache_len).astype(jnp.int32)               # (B,)
    row = (jnp.arange(b), slot)
    if layer is not None:
        row = (layer,) + row
    k_cache = cache["k"].at[row].set(_pad_row(k[:, 0], width))
    v_cache = cache["v"].at[row].set(_pad_row(v[:, 0], width))
    kv_pos = cache["pos"].at[row].set(q_pos.astype(jnp.int32))
    out = ops.decode_attention(q[:, 0], k_cache, v_cache, kv_pos,
                               q_pos.astype(jnp.int32), window=spec.window,
                               softcap=spec.softcap, scale=spec.scale,
                               layer=layer)
    y = _proj_out(out, params["wo"])[:, None, :]               # (B, 1, d)
    return y, {"k": k_cache, "v": v_cache, "pos": kv_pos}


def cross_attention_init(key, spec: AttnSpec, dtype) -> dict:
    return attention_init(key, spec, dtype)


def cross_attention(params: dict, spec: AttnSpec, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Decoder cross-attention against precomputed encoder K/V."""
    b, s, _ = x.shape
    q = _proj_heads(x, params["wq"])
    out = ops.attention(q, enc_k, enc_v, causal=False, window=0,
                        softcap=spec.softcap, scale=spec.scale,
                        segment_pos=jnp.broadcast_to(
                            jnp.full((1,), enc_k.shape[1] - 1, jnp.int32),
                            (b, s)))
    return _proj_out(out, params["wo"])


def cross_kv(params: dict, spec: AttnSpec, enc_out: jax.Array):
    k = _proj_heads(enc_out, params["wk"])
    v = _proj_heads(enc_out, params["wv"])
    return k, v


# ------------------------------------------------------------------ MLPs
def mlp_init(key, d: int, d_ff: int, kind: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"wi": _dense_init(k1, (d, d_ff), d, dtype),
                "wg": _dense_init(k2, (d, d_ff), d, dtype),
                "wo": _dense_init(k3, (d_ff, d), d_ff, dtype)}
    # non-gated: relu2 (nemotron squared-ReLU) or gelu
    return {"wi": _dense_init(k1, (d, d_ff), d, dtype),
            "wo": _dense_init(k3, (d_ff, d), d_ff, dtype)}


def mlp(params: dict, x: jax.Array, kind: str) -> jax.Array:
    h = matmul(x, params["wi"])
    if kind == "swiglu":
        h = jax.nn.silu(matmul(x, params["wg"]).astype(jnp.float32)).astype(x.dtype) * h
    elif kind == "geglu":
        h = jax.nn.gelu(matmul(x, params["wg"]).astype(jnp.float32),
                        approximate=True).astype(x.dtype) * h
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
    elif kind == "gelu":
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    else:
        raise ValueError(f"unknown mlp kind {kind}")
    return matmul(h, params["wo"])


# ------------------------------------------------------------------- MoE
def moe_init(key, d: int, d_ff: int, n_experts: int, kind: str, dtype) -> dict:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    p = {
        "router": _dense_init(kr, (d, n_experts), d, jnp.float32),
        "wi": _dense_init(k1, (n_experts, d, d_ff), d, dtype),
        "wo": _dense_init(k3, (n_experts, d_ff, d), d_ff, dtype),
    }
    if kind in ("swiglu", "geglu"):
        p["wg"] = _dense_init(k2, (n_experts, d, d_ff), d, dtype)
    return p


def _expert_hidden(g: jax.Array, h: jax.Array, kind: str) -> jax.Array:
    """The experts' activation of the up projection ``h`` (and, gated,
    of the gate projection ``g``), in f32, cast back to h's dtype."""
    if kind == "swiglu":
        return (jax.nn.silu(g.astype(jnp.float32))
                * h.astype(jnp.float32)).astype(h.dtype)
    if kind == "geglu":
        return (jax.nn.gelu(g.astype(jnp.float32), approximate=True)
                * h.astype(jnp.float32)).astype(h.dtype)
    if kind == "relu2":
        return jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(h.dtype)
    if kind == "gelu":
        return jax.nn.gelu(h.astype(jnp.float32),
                           approximate=True).astype(h.dtype)
    raise ValueError(f"unknown mlp kind {kind}")


def moe_dropless(params: dict, x: jax.Array, *, top_k: int, kind: str,
                 layer: Optional[jax.Array] = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Dropless top-k MoE, the serving path: every token reaches all of
    its ``top_k`` experts, however unevenly the router spreads them.

    The router runs in f32 at full precision: softmax over the experts,
    top-k, the k gates renormalised to sum to 1. The tokens' T*k rows
    are sorted by expert and go through ``ops.moe_gmm`` for the up (and
    gate) and the down projection, which reads only the experts that
    received rows; each token's output is the gate-weighted sum of its k
    rows, in f32.

    x: (B, S, d). With ``layer`` (an int32 scalar) ``params`` holds a
    stack of layers' MoE weights and layer ``layer``'s are used, the
    expert matrices read where they lie (``ops.moe_gmm``). Returns
    (output, stats): stats is int32 (2,), the experts that received at
    least one row and the rows routed (T*k, read back from the group
    sizes the kernel was given)."""
    b, s, d = x.shape
    e = params["router"].shape[-1]
    router = params["router"]
    if layer is not None:
        router = jax.lax.dynamic_index_in_dim(router, layer, 0,
                                              keepdims=False)
    xf = x.reshape(b * s, d)
    logits = jnp.dot(xf.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, top_k)                   # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    flat = expert.reshape(-1)                                    # (T*k,)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    rows = xf[order // top_k]                                    # sorted
    h = ops.moe_gmm(rows, params["wi"], sizes, layer)
    g = (ops.moe_gmm(rows, params["wg"], sizes, layer) if "wg" in params
         else None)
    out = ops.moe_gmm(_expert_hidden(g, h, kind), params["wo"], sizes, layer)

    # back to token order (row j of the (T, k) layout sits at rank[j]),
    # weighted and summed over k in f32: one fusion, no f32 copy of rows
    rank = jnp.argsort(order)
    y = jnp.sum(out[rank].reshape(b * s, top_k, d).astype(jnp.float32)
                * gate[..., None], axis=1)
    stats = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes)]).astype(jnp.int32)
    return y.astype(x.dtype).reshape(b, s, d), stats


def moe(params: dict, x: jax.Array, *, top_k: int, kind: str,
        capacity_factor: float = 1.25) -> tuple[jax.Array, jax.Array]:
    """Capacity top-k MoE, the training and dry-run path: data-local
    grouped dispatch + expert-parallel FFN (sort-based, gather/scatter
    kept *within* a token group). Each expert takes at most
    ``round(Tg * top_k / E * capacity_factor)`` rows of a group; the
    rest are dropped (serving uses ``moe_dropless``).

    x: (B, S, d). Returns (output, aux_loss) with the Switch-style
    load-balance loss. Tokens are split into ``dist.moe_num_groups()``
    groups aligned with the data shards (1 on CPU/tests): argsort, rank
    and scatter then never cross a shard boundary, so under GSPMD the
    dispatch is fully data-parallel and the only cross-device traffic is
    the expert einsum's weight all-gather (see EXPERIMENTS §Perf,
    iteration 'dbrx-moe').
    """
    b, s, d = x.shape
    t = b * s
    e = params["router"].shape[1]
    groups = dist.moe_num_groups()
    if t % groups != 0:
        groups = 1
    tg = t // groups
    xf = x.reshape(groups, tg, d)
    xf = dist.constrain_moe_groups(xf)

    logits = jnp.einsum("gtd,de->gte", xf.astype(jnp.float32),
                        params["router"],
                        preferred_element_type=jnp.float32)     # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)            # (G, Tg, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch Transformers eq. 4), over all tokens
    me = jnp.mean(probs, axis=(0, 1))                            # (E,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, e, dtype=jnp.float32),
                          axis=2), axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    cap = int(max(1, round(tg * top_k / e * capacity_factor)))

    def dispatch_one(xg, idxg, gateg):
        """Per-group sort-based dispatch. xg: (Tg, d); idxg/gateg: (Tg, k)."""
        flat_expert = idxg.reshape(-1)                           # (Tg*k,)
        flat_token = jnp.repeat(jnp.arange(tg), top_k)
        flat_gate = gateg.reshape(-1)
        order = jnp.argsort(flat_expert, stable=True)
        se, st_tok, sg = flat_expert[order], flat_token[order], flat_gate[order]
        same = jax.nn.one_hot(se, e, dtype=jnp.int32)
        rank = jnp.cumsum(same, axis=0) - 1
        pos_in_expert = jnp.take_along_axis(rank, se[:, None], axis=1)[:, 0]
        keep = pos_in_expert < cap
        slot = se * cap + jnp.clip(pos_in_expert, 0, cap - 1)
        buf = jnp.zeros((e * cap, d), x.dtype)
        buf = buf.at[jnp.where(keep, slot, e * cap - 1)].add(
            jnp.where(keep[:, None], xg[st_tok], 0).astype(x.dtype))
        return buf.reshape(e, cap, d), (slot, st_tok, sg, keep)

    buf, combine_info = jax.vmap(dispatch_one)(xf, gate_idx, gate_vals)
    buf = dist.constrain_moe_buffer(buf)      # (G, E, C, d): G->data, E->model

    # ---- expert FFN (batched over groups and experts) ------------------
    # weights re-constrained to expert-parallel at compute time so the
    # d contraction stays local (storage may be FSDP-sharded)
    wi = dist.constrain_moe_weight(params["wi"])
    h = jnp.einsum("gecd,edf->gecf", buf, wi,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    g = None
    if kind in ("swiglu", "geglu"):
        g = jnp.einsum("gecd,edf->gecf", buf,
                       dist.constrain_moe_weight(params["wg"]),
                       preferred_element_type=jnp.float32)
    h = _expert_hidden(g, h, kind)
    out_e = jnp.einsum("gecf,efd->gecd", h,
                       dist.constrain_moe_weight(params["wo"]),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    out_e = dist.constrain_moe_buffer(out_e)

    # ---- combine back (per group) --------------------------------------
    def combine_one(oute, info):
        slot, st_tok, sg, keep = info
        out_flat = oute.reshape(e * cap, d)
        gathered = out_flat[slot] * (sg * keep)[:, None].astype(x.dtype)
        return jnp.zeros((tg, d), x.dtype).at[st_tok].add(gathered)

    y = jax.vmap(combine_one)(out_e, combine_info)
    y = dist.constrain_moe_groups(y)
    return y.reshape(b, s, d), aux
