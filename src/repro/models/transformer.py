"""Decoder-only transformer assembly for all non-enc-dec architectures.

Layers are grouped into *pattern periods* (e.g. gemma2's (local, global),
recurrentgemma's (rglru, rglru, local)); parameters are stacked across
periods and the forward pass is a ``lax.scan`` over periods with the
period body optionally rematerialised. This keeps the lowered HLO small
(one period body regardless of depth — essential for the 96-layer dry-run
configs) and handles heterogeneous layer kinds, since every period has
identical structure. Layers left over when n_layers % period != 0
(recurrentgemma: 26 = 8*3 + 2) are unrolled after the scan.

Three entry points per model: ``forward`` (train: full logits),
``prefill`` (full-sequence + cache out), ``decode_step`` (one token).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain_batch
from repro.models import layers, rglru, ssm

PyTree = Any


# --------------------------------------------------------------- helpers
def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def attn_spec(cfg: ArchConfig, kind: str) -> layers.AttnSpec:
    if kind == "local":
        window = cfg.window
    else:
        window = cfg.global_window  # 0 = truly global
    return layers.AttnSpec(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, window=window,
        softcap=cfg.attn_softcap, causal=True, use_rope=cfg.use_rope,
        qk_norm=cfg.qk_norm, scale=cfg.attn_scale,
        yarn=cfg.global_yarn if kind == "attn" else None)


def cache_len_for(cfg: ArchConfig, kind: str, max_len: int) -> int:
    if kind == "local":
        return min(cfg.window, max_len)
    if cfg.global_window > 0:
        return min(cfg.global_window, max_len)
    return max_len


def _has_mlp(cfg: ArchConfig, kind: str) -> bool:
    # Mamba-2 blocks are the whole layer; attention/rglru layers carry an MLP.
    return cfg.d_ff > 0 and kind != "mamba2"


def _layer_kinds(cfg: ArchConfig) -> list:
    """Every layer's kind in order: the scanned periods, the remainder."""
    return (list(cfg.layer_pattern) * cfg.n_periods
            + list(cfg.layer_pattern[: cfg.n_remainder_layers]))


def init_moe_counts(cfg: ArchConfig) -> Optional[jax.Array]:
    """Zeroed decode-step MoE counters, int32 (MoE layers, 2): per MoE
    layer, in layer order, the experts that received a row and the rows
    routed, each summed over decode steps (see ``decode_step``). None
    for a model without experts."""
    if cfg.n_experts == 0:
        return None
    n = sum(_has_mlp(cfg, kind) for kind in _layer_kinds(cfg))
    return jnp.zeros((n, 2), jnp.int32)


# ------------------------------------------------------------------ init
def layer_init(key, cfg: ArchConfig, kind: str) -> dict:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 4)
    p: dict = {"norm1": layers.norm_init(cfg.norm, cfg.d_model)}
    if kind in ("attn", "local"):
        p["attn"] = layers.attention_init(keys[0], attn_spec(cfg, kind), dt)
    elif kind == "mamba2":
        p["mixer"] = ssm.init(keys[0], cfg, dt)
    elif kind == "rglru":
        p["mixer"] = rglru.init(keys[0], cfg, dt)
    else:
        raise ValueError(f"unknown layer kind {kind}")
    if _has_mlp(cfg, kind):
        p["norm2"] = layers.norm_init(cfg.norm, cfg.d_model)
        if cfg.n_experts > 0:
            p["moe"] = layers.moe_init(keys[1], cfg.d_model, cfg.d_ff,
                                       cfg.n_experts, cfg.mlp_kind, dt)
            if cfg.dense_residual:
                p["dense_mlp"] = layers.mlp_init(keys[2], cfg.d_model,
                                                 cfg.d_ff, cfg.mlp_kind, dt)
        else:
            p["mlp"] = layers.mlp_init(keys[1], cfg.d_model, cfg.d_ff,
                                       cfg.mlp_kind, dt)
    return p


def init_params(key, cfg: ArchConfig) -> PyTree:
    dt = _dtype(cfg)
    k_embed, k_blocks, k_rem, k_head = jax.random.split(key, 4)
    params: dict = {}
    params["embed"] = (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model),
                                         jnp.float32)
                       * cfg.d_model ** -0.5).astype(dt)
    # stacked per-period blocks
    if cfg.n_periods > 0:
        def one_period(k):
            ks = jax.random.split(k, cfg.period)
            return {f"layer{j}": layer_init(ks[j], cfg, kind)
                    for j, kind in enumerate(cfg.layer_pattern)}
        # vmap over the period keys draws exactly the values a loop over
        # them would, but traces one period: a 32-layer init then
        # compiles in seconds instead of minutes
        period_keys = jax.random.split(k_blocks, cfg.n_periods)
        params["blocks"] = jax.vmap(one_period)(period_keys)
    rem_kinds = cfg.layer_pattern[: cfg.n_remainder_layers]
    if rem_kinds:
        ks = jax.random.split(k_rem, len(rem_kinds))
        params["remainder"] = [layer_init(ks[j], cfg, kind)
                               for j, kind in enumerate(rem_kinds)]
    params["final_norm"] = layers.norm_init(cfg.norm, cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers._dense_init(
            k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, dt)
    return params


# --------------------------------------------------------------- forward
def _mlp_block(p: dict, cfg: ArchConfig, kind: str, x: jax.Array,
               dropless: bool, expert_layer: Optional[jax.Array] = None):
    """The layer's second half, pre-norm residual: x plus the MLP, or the
    MoE (with the dense residual MLP beside it, Arctic), of norm2(x).
    ``dropless`` picks the serving MoE (``layers.moe_dropless``) over
    the capacity one that training and the dry run lower;
    ``expert_layer``: this layer's index in the period-stacked MoE
    weights ``p["moe"]`` holds (see ``_split_experts``). Returns (x,
    the capacity MoE's load-balance loss or None, the dropless MoE's
    stats or None)."""
    if not _has_mlp(cfg, kind):
        return x, None, None
    h = layers.apply_norm(cfg.norm, p["norm2"], x)
    if cfg.n_experts == 0:
        return x + layers.mlp(p["mlp"], h, cfg.mlp_kind), None, None
    aux = stats = None
    if dropless:
        y, stats = layers.moe_dropless(p["moe"], h, top_k=cfg.top_k,
                                       kind=cfg.mlp_kind, layer=expert_layer)
    else:
        y, aux = layers.moe(p["moe"], h, top_k=cfg.top_k, kind=cfg.mlp_kind,
                            capacity_factor=cfg.capacity_factor)
    if cfg.dense_residual:
        y = y + layers.mlp(p["dense_mlp"], h, cfg.mlp_kind)
    return x + y, aux, stats


def _split_experts(blocks: dict, cfg: ArchConfig, dropless: bool):
    """For a scan over periods with the dropless MoE: (the period blocks
    without their MoE weights, for the scan to slice, and each layer's
    MoE weights stacked over periods, which ``moe_gmm`` reads in place
    at the period's index). Sliced by the scan, every expert of a layer
    would be copied out of the stack each step, touched or not: a
    Mosaic kernel's operand is a buffer of its own."""
    if not (dropless and cfg.n_experts):
        return blocks, {}
    scanned = {name: {k: v for k, v in blk.items() if k != "moe"}
               for name, blk in blocks.items()}
    return scanned, {name: blk["moe"] for name, blk in blocks.items()
                     if "moe" in blk}


def _with_experts(block: dict, experts: dict, name: str) -> dict:
    return dict(block, moe=experts[name]) if name in experts else block


def _apply_layer(p: dict, cfg: ArchConfig, kind: str, x: jax.Array,
                 positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pre-norm residual layer, the training path (capacity MoE).
    Returns (x, aux_loss)."""
    zero = jnp.zeros((), jnp.float32)
    h = layers.apply_norm(cfg.norm, p["norm1"], x)
    if kind in ("attn", "local"):
        x = x + layers.self_attention(p["attn"], attn_spec(cfg, kind), h,
                                      positions)
    elif kind == "mamba2":
        x = x + ssm.forward(p["mixer"], cfg, h)
    elif kind == "rglru":
        x = x + rglru.forward(p["mixer"], cfg, h)
    x, aux, _ = _mlp_block(p, cfg, kind, x, dropless=False)
    return x, zero if aux is None else aux


def _embed(params, cfg: ArchConfig, tokens_or_embeddings: jax.Array):
    if cfg.frontend == "embeddings" or tokens_or_embeddings.ndim == 3:
        return tokens_or_embeddings.astype(_dtype(cfg))
    return params["embed"][tokens_or_embeddings]


def _logits(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jax.lax.dot_general(
        x, head, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if cfg.final_softcap > 0:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


def forward(params: PyTree, cfg: ArchConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """Training forward: (B, S) tokens -> (B, S, V) fp32 logits, aux loss."""
    x = constrain_batch(_embed(params, cfg, tokens))
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def period_body(carry, block):
        x, aux = carry
        x = constrain_batch(x)
        for j, kind in enumerate(cfg.layer_pattern):
            x, a = _apply_layer(block[f"layer{j}"], cfg, kind, x, positions)
            aux = aux + a
        return (x, aux), None

    aux = jnp.zeros((), jnp.float32)
    if cfg.n_periods > 0:
        body = period_body
        if cfg.remat:
            body = jax.checkpoint(period_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), _ = jax.lax.scan(body, (x, aux), params["blocks"])
    for j, p in enumerate(params.get("remainder", [])):
        x, a = _apply_layer(p, cfg, cfg.layer_pattern[j], x, positions)
        aux = aux + a
    return _logits(params, cfg, x), aux


# ---------------------------------------------------------------- caches
def init_layer_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int):
    dt = _dtype(cfg)
    if kind in ("attn", "local"):
        c = cache_len_for(cfg, kind, max_len)
        w = layers.kv_row_width(cfg.head_dim)
        return {
            "k": jnp.zeros((batch, c, cfg.n_kv_heads, w), dt),
            "v": jnp.zeros((batch, c, cfg.n_kv_heads, w), dt),
            "pos": jnp.full((batch, c), -1, jnp.int32),
        }
    if kind == "mamba2":
        return ssm.init_state(cfg, batch, dt)
    if kind == "rglru":
        return rglru.init_state(cfg, batch, dt)
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    cache: dict = {}
    if cfg.n_periods > 0:
        def one(kind):
            c = init_layer_cache(cfg, kind, batch, max_len)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_periods,) + a.shape), c)
        cache["blocks"] = {f"layer{j}": one(kind)
                           for j, kind in enumerate(cfg.layer_pattern)}
    rem = cfg.layer_pattern[: cfg.n_remainder_layers]
    if rem:
        cache["remainder"] = [init_layer_cache(cfg, kind, batch, max_len)
                              for kind in rem]
    return cache


# ---------------------------------------------------------------- prefill
def _apply_layer_prefill(p, cfg, kind, x, positions, max_len,
                         dropless=True, expert_layer=None):
    h = layers.apply_norm(cfg.norm, p["norm1"], x)
    if kind in ("attn", "local"):
        y, cache = layers.self_attention_prefill(
            p["attn"], attn_spec(cfg, kind), h, positions,
            cache_len_for(cfg, kind, max_len))
    else:
        mod = ssm if kind == "mamba2" else rglru
        y, cache = mod.forward(p["mixer"], cfg, h, return_state=True)
    x, _, _ = _mlp_block(p, cfg, kind, x + y, dropless, expert_layer)
    return x, cache


def prefill(params: PyTree, cfg: ArchConfig, tokens: jax.Array,
            max_len: Optional[int] = None, *,
            moe_dropless: bool = True) -> tuple[jax.Array, PyTree]:
    """Prefill pass: returns (last-token fp32 logits (B, V), cache).
    ``moe_dropless=False`` lowers the capacity MoE instead (the dry
    run)."""
    x = constrain_batch(_embed(params, cfg, tokens))
    b, s = x.shape[:2]
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def period_body(x, scanned):
        block, i = scanned
        x = constrain_batch(x)
        caches = {}
        for j, kind in enumerate(cfg.layer_pattern):
            name = f"layer{j}"
            x, caches[name] = _apply_layer_prefill(
                _with_experts(block[name], experts, name), cfg, kind, x,
                positions, max_len, moe_dropless,
                i if name in experts else None)
        return x, caches

    cache: dict = {}
    if cfg.n_periods > 0:
        blocks, experts = _split_experts(params["blocks"], cfg, moe_dropless)
        body = period_body
        if cfg.remat:
            body = jax.checkpoint(period_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        x, cache["blocks"] = jax.lax.scan(
            body, x, (blocks, jnp.arange(cfg.n_periods) if experts else None))
    rem = cfg.layer_pattern[: cfg.n_remainder_layers]
    if rem:
        cache["remainder"] = []
        for j, p in enumerate(params["remainder"]):
            x, c = _apply_layer_prefill(p, cfg, rem[j], x, positions,
                                        max_len, moe_dropless)
            cache["remainder"].append(c)
    logits = _logits(params, cfg, x[:, -1:, :])[:, 0, :]
    return logits, cache


# ----------------------------------------------------------------- decode
def _apply_layer_decode(p, cfg, kind, x, cache, q_pos, layer=None,
                        dropless=True, expert_layer=None):
    """``layer``: this attention layer's index in a layer-stacked
    ``cache`` (see ``layers.self_attention_decode``); None for a cache of
    its own. ``expert_layer``: see ``_mlp_block``. Returns (x, cache,
    the dropless MoE's stats or None)."""
    h = layers.apply_norm(cfg.norm, p["norm1"], x)
    if kind in ("attn", "local"):
        y, cache = layers.self_attention_decode(
            p["attn"], attn_spec(cfg, kind), h, cache, q_pos, layer)
    else:
        mod = ssm if kind == "mamba2" else rglru
        y, cache = mod.decode_step(p["mixer"], cfg, h, cache)
    x, _, stats = _mlp_block(p, cfg, kind, x + y, dropless, expert_layer)
    return x, cache, stats


def decode_step(params: PyTree, cfg: ArchConfig, tokens: jax.Array,
                cache: PyTree, pos: jax.Array, *, moe_dropless: bool = True,
                moe_counts: Optional[jax.Array] = None):
    """One decode step. tokens: (B,) int32 (or (B, d) embeddings);
    pos: (B,) absolute positions. Returns ((B, V) fp32 logits, new cache).

    ``moe_dropless=False`` lowers the capacity MoE instead (the dry
    run). With ``moe_counts`` (``init_moe_counts``; dropless MoE only)
    the step also returns them with this step's stats added: per MoE
    layer, the experts that received at least one of the B*top_k rows,
    and the rows routed."""
    count = moe_counts is not None
    if tokens.ndim == 1 and cfg.frontend == "tokens":
        x = params["embed"][tokens][:, None, :]
    else:
        x = tokens.astype(_dtype(cfg))[:, None, :]

    def period_body(carry, scanned):
        # The stacked cache rides in the CARRY, NOT as scan xs/ys: xs+ys
        # would make the cache both a loop input and a separately-allocated
        # output, which XLA cannot alias — it then copies the whole
        # multi-GB KV stack every layer (measured 2x927 GB/step on
        # nemotron decode_32k; see EXPERIMENTS §Perf iteration
        # 'nemo-decode-2'). Attention layers write the new token's K/V row
        # and position into the stack where it lies, and the kernel reads
        # the layer from the stack: nothing layer-sized is sliced out or
        # written back. With the cache donated at the jit boundary, as
        # ServingEngine does, and its rows padded to whole lane tiles
        # (layers.kv_row_width), the stack is not copied either.
        # Recurrent states, O(d) a slot, are sliced out and written back.
        x, cache_all = carry
        x = constrain_batch(x)
        block, i = scanned
        cache_all = dict(cache_all)
        stats = []
        for j, kind in enumerate(cfg.layer_pattern):
            name = f"layer{j}"
            p = _with_experts(block[name], experts, name)
            at = i if name in experts else None
            if kind in ("attn", "local"):
                x, cache_all[name], st = _apply_layer_decode(
                    p, cfg, kind, x, cache_all[name], pos, layer=i,
                    dropless=moe_dropless, expert_layer=at)
            else:
                c = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                           keepdims=False),
                    cache_all[name])
                x, c, st = _apply_layer_decode(p, cfg, kind, x, c, pos,
                                               dropless=moe_dropless,
                                               expert_layer=at)
                cache_all[name] = jax.tree.map(
                    lambda a, u: jax.lax.dynamic_update_index_in_dim(
                        a, u, i, 0), cache_all[name], c)
            if count and st is not None:
                stats.append(st)
        return (x, cache_all), (jnp.stack(stats) if count else None)

    new_cache: dict = {}
    stats = []
    if cfg.n_periods > 0:
        blocks, experts = _split_experts(params["blocks"], cfg, moe_dropless)
        (x, new_cache["blocks"]), per_period = jax.lax.scan(
            period_body, (x, cache["blocks"]),
            (blocks, jnp.arange(cfg.n_periods)))
        if count:
            stats.append(per_period.reshape(-1, 2))
    rem = cfg.layer_pattern[: cfg.n_remainder_layers]
    if rem:
        new_cache["remainder"] = []
        for j, p in enumerate(params["remainder"]):
            x, c, st = _apply_layer_decode(p, cfg, rem[j], x,
                                           cache["remainder"][j], pos,
                                           dropless=moe_dropless)
            new_cache["remainder"].append(c)
            if count and st is not None:
                stats.append(st[None])
    logits = _logits(params, cfg, x)[:, 0, :]
    if count:
        return logits, new_cache, moe_counts + jnp.concatenate(stats)
    return logits, new_cache
