"""Single-token (flash-decode) attention against a ring-buffered KV cache.

One new query per sequence attends to a cache of C slots whose absolute
positions arrive as a side input (``kv_pos``; -1 = never written). The
kernel tiles the cache sequence into VMEM blocks of every head at once
(so the TPU block layout is legal for any head count and head_dim) and
carries each head's online softmax state (m, l, acc) across the
kv-block grid axis — the TPU-native
flash-decode: the cache streams HBM->VMEM exactly once, and the fp32
accumulator never leaves VMEM.

GQA by looping over kv heads: kv head g's K and V are sliced out of the
block and upcast once, and its rep = H // Hkv query heads (rows
g·rep … (g+1)·rep) attend them as one group, one score dot and one value
dot (at rep 1, one query head at a time). Validity masking from kv_pos
(handles ring-buffer wraparound and sliding windows without any
position arithmetic in the layer code).

Two entry points run the same kernel body: ``decode_attention`` over one
layer's (B, C, Hkv, W) cache, and ``decode_attention_stacked`` over the
decode step's (L, B, C, Hkv, W) stack plus a layer index, which reads
the layer in place. A cache row may be wider than the head dim D (the
model pads rows to whole lane tiles, ``models.layers.kv_row_width``);
the kernel attends the first D lanes.

Oracle: ``repro.kernels.ref.decode_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, kvpos_ref, qpos_ref, o_ref,
            m_ref, l_ref, acc_ref, **kw):
    _attend(kvpos_ref[0], q_ref, k_ref, v_ref, qpos_ref, o_ref,  # (1, bkv)
            m_ref, l_ref, acc_ref, **kw)


def _stacked_kernel(layer_ref, q_ref, k_ref, v_ref, kvpos_ref, qpos_ref,
                    o_ref, m_ref, l_ref, acc_ref, **kw):
    del layer_ref                       # read by the index maps
    # the positions block holds every sequence's row (a block of one row
    # of a (B, C) plane is not a legal TPU tile): take this sequence's
    kv_pos = kvpos_ref[pl.ds(pl.program_id(0), 1), :]          # (1, bkv)
    _attend(kv_pos, q_ref, k_ref, v_ref, qpos_ref, o_ref,
            m_ref, l_ref, acc_ref, **kw)


def _attend(kv_pos, q_ref, k_ref, v_ref, qpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, window: int,
            softcap: float, rep: int, n_kv_blocks: int):
    ikv = pl.program_id(1)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qpos_ref[0]                         # (1, 1)
    valid = (kv_pos >= 0) & (kv_pos <= q_pos)
    if window > 0:
        valid &= kv_pos > (q_pos - window)

    d = q_ref.shape[2]                      # the cache rows may be wider
    for g in range(k_ref.shape[2]):
        rows = slice(g * rep, (g + 1) * rep)  # kv head g's query group
        q = q_ref[0, rows, :].astype(jnp.float32)             # (rep, D)
        k = k_ref[0, :, g, :d].astype(jnp.float32)            # (bkv, D)
        v = v_ref[0, :, g, :d].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)                      # (rep, bkv)

        m_prev = m_ref[rows, :]                               # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = l_ref[rows, :] * corr + jnp.sum(
            p, axis=1, keepdims=True)
        acc_ref[rows, :] = acc_ref[rows, :] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rows, :] = m_new

    @pl.when(ikv == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "block_kv",
                              "interpret"))
def decode_attention(q, k_cache, v_cache, kv_pos, q_pos, *, window: int = 0,
                     softcap: float = 0.0, scale=None, block_kv: int = 512,
                     interpret: bool = False):
    """q: (B, H, D); k_cache/v_cache: (B, C, Hkv, W), W >= D (rows past
    D are padding, never read); kv_pos: (B, C); q_pos: (B,). Returns
    (B, H, D)."""
    b, h, d = q.shape
    _, c, hkv, w = k_cache.shape
    rep = h // hkv
    scale = float(d ** -0.5 if scale is None else scale)
    block_kv = min(block_kv, c)
    assert c % block_kv == 0, (c, block_kv)
    n_kv = c // block_kv
    grid = (b, n_kv)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               softcap=softcap, rep=rep, n_kv_blocks=n_kv)
    # one grid step holds every head of a kv block: the last two block
    # dims are then whole (H, D) / (Hkv, W) planes, which the TPU tiles
    # for any head count and head_dim; positions ride as (1, 1, .) rows
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bb, ikv: (bb, 0, 0)),
            pl.BlockSpec((1, block_kv, hkv, w),
                         lambda bb, ikv: (bb, ikv, 0, 0)),
            pl.BlockSpec((1, block_kv, hkv, w),
                         lambda bb, ikv: (bb, ikv, 0, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bb, ikv: (bb, 0, ikv)),
            pl.BlockSpec((1, 1, 1), lambda bb, ikv: (bb, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bb, ikv: (bb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),      # m
            pltpu.VMEM((h, 1), jnp.float32),      # l
            pltpu.VMEM((h, d), jnp.float32),      # acc
        ],
        # double-buffered k and v blocks of 512 x (32, 80->128 lanes)
        # bf16 take ~17 MB, over the 16 MB default scoped VMEM
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(q, k_cache, v_cache, kv_pos.reshape(b, 1, c), q_pos.reshape(b, 1, 1))


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "block_kv",
                              "interpret"))
def decode_attention_stacked(q, k_cache, v_cache, kv_pos, q_pos, layer, *,
                             window: int = 0, softcap: float = 0.0,
                             scale=None, block_kv: int = 512,
                             interpret: bool = False):
    """``decode_attention`` against one layer of a layer-stacked cache,
    read where it lies. q: (B, H, D); k_cache/v_cache: (L, B, C, Hkv, W),
    W >= D; kv_pos: (L, B, C); q_pos: (B,); layer: int32 scalar. Returns
    (B, H, D).

    The layer index is a scalar-prefetch operand: the index maps select
    the layer's blocks on a squeezed leading axis, so the layer's slab is
    never sliced out of the stack or copied."""
    b, h, d = q.shape
    _, _, c, hkv, w = k_cache.shape
    rep = h // hkv
    scale = float(d ** -0.5 if scale is None else scale)
    block_kv = min(block_kv, c)
    assert c % block_kv == 0, (c, block_kv)
    n_kv = c // block_kv

    kernel = functools.partial(_stacked_kernel, scale=scale, window=window,
                               softcap=softcap, rep=rep, n_kv_blocks=n_kv)
    kv_spec = pl.BlockSpec((None, 1, block_kv, hkv, w),
                           lambda bb, ikv, layer: (layer[0], bb, ikv, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bb, ikv, layer: (bb, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((None, b, block_kv),
                         lambda bb, ikv, layer: (layer[0], 0, ikv)),
            pl.BlockSpec((1, 1, 1), lambda bb, ikv, layer: (bb, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bb, ikv, layer: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),      # m
            pltpu.VMEM((h, 1), jnp.float32),      # l
            pltpu.VMEM((h, d), jnp.float32),      # acc
        ],
    )
    # named as the 4-D entry's instruction is, so that a profile finds
    # both under the kernel's name
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_cache, v_cache,
      kv_pos, q_pos.reshape(b, 1, 1))
