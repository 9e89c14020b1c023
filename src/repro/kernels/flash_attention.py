"""Blockwise online-softmax attention (flash attention) for TPU.

TPU adaptation (not a CUDA port): the kernel is expressed as a Pallas
grid over (batch, q-head, q-block, kv-block) with explicit VMEM
BlockSpecs over heads-major (B, H, S, D) views of the BSHD inputs. The MXU sees (block_q x D) @ (D x block_kv) tiles —
block sizes default to 128 to match the 128x128 systolic array — and
the online-softmax running state (m, l, acc) lives in VMEM scratch,
carried across the kv-block grid axis (TPU grids iterate the minor axis
sequentially, so the carry is race-free by construction).

GQA is handled in the index_map (q-head h reads kv-head h // rep), so
no head-repeated copies of K/V are ever materialised.

Supports: causal masking, sliding window, logit soft-capping (gemma2).
Assumes contiguous query positions suffix-aligned to the kv sequence
(qpos = Skv - Sq + iq) — exactly what training/prefill use.

Oracle: ``repro.kernels.ref.attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            block_q: int, block_kv: int, q_offset: int, n_kv_blocks: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                            # (bq, D)
    k = k_ref[0, 0]                            # (bkv, D)
    v = v_ref[0, 0]

    s = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kpos = ikv * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                        # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ikv == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale=None, segment_pos=None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D). segment_pos is accepted
    for API parity with the ref; the kernel assumes suffix-aligned
    contiguous positions (the only pattern the models use)."""
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    rep = h // hkv
    scale = float(d ** -0.5 if scale is None else scale)
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0, (sq, skv)
    n_kv = skv // block_kv
    grid = (b, h, sq // block_q, n_kv)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, q_offset=skv - sq,
        n_kv_blocks=n_kv)

    # heads-major views: a block's last two dims are then (sequence, D),
    # which the TPU tiles for any head_dim (a (1, D) head slice of BSHD
    # is not a legal block unless H == 1)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, iq, ikv: (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, iq, ikv: (bb, hh // rep, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, iq, ikv: (bb, hh // rep, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, iq, ikv: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m: running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # l: running sum
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
