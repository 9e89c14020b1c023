"""Fused (flash-style) attention in pure JAX with a custom VJP.

The XLA-portable twin of the Pallas ``flash_attention`` kernel: an
online-softmax scan over KV blocks that never materialises the (Sq x Skv)
logits and never repeats K/V across GQA groups (grouped einsum instead).
Because it is plain jnp + lax.scan it lowers for ANY backend — the
multi-pod dry-run uses it to model what the TPU kernel does to the
memory roofline term (EXPERIMENTS §Perf).

The custom VJP implements the flash-attention backward: save only
(out, rowmax m, rowsum l) from the forward and recompute per-block
probabilities in the backward scan — O(S x block) live memory instead of
O(S^2). Without this, differentiating the forward scan would stash every
block's partial accumulator and erase the benefit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30
DEFAULT_BLOCK = 512


def _prep(q, k, v, scale):
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    rep = h // hkv
    qg = q.reshape(b, sq, hkv, rep, d).astype(jnp.float32) * scale
    return qg, k.astype(jnp.float32), v.astype(jnp.float32), rep


def _block_logits(qg, kb, softcap, qpos, kpos, causal, window):
    """qg: (B,Sq,G,R,D); kb: (B,bk,G,D) -> logits (B,G,R,Sq,bk), mask."""
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kb)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    mask = jnp.ones(qpos.shape[:1] + (qpos.shape[1], kpos.shape[0]), bool)
    if causal:
        mask &= kpos[None, None, :] <= qpos[:, :, None]
    if window > 0:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    return jnp.where(mask[:, None, None, :, :], s, NEG_INF)


def _forward(q, k, v, causal, window, softcap, scale, segment_pos, block_kv):
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    qg, kf, vf, rep = _prep(q, k, v, scale)
    nb = skv // block_kv
    kb = kf.reshape(b, nb, block_kv, hkv, d)
    vb = vf.reshape(b, nb, block_kv, hkv, d)
    if segment_pos is None:
        qpos = jnp.broadcast_to(jnp.arange(sq)[None, :] + (skv - sq), (b, sq))
    else:
        qpos = segment_pos

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, ib = blk
        kpos = ib * block_kv + jnp.arange(block_kv)
        s = _block_logits(qg, kblk, softcap, qpos, kpos, causal, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bgrqk,bkgd->bgrqd", p, vblk)
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, rep, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,G,R,Sq,D)
    out_bshd = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, d).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))         # logsumexp rows
    return out_bshd, (lse, out)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 8))
def fused_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    scale=None, segment_pos=None, block_kv=DEFAULT_BLOCK):
    """Same semantics as kernels.ref.attention; O(S*block) memory."""
    d = q.shape[-1]
    scale_val = float(d ** -0.5) if scale is None else float(scale)
    out, _ = _forward(q, k, v, causal, window, softcap, scale_val,
                      segment_pos, min(block_kv, k.shape[1]))
    return out


def _fwd(q, k, v, causal, window, softcap, scale, segment_pos, block_kv):
    d = q.shape[-1]
    scale_val = float(d ** -0.5) if scale is None else float(scale)
    bk = min(block_kv, k.shape[1])
    out, (lse, _) = _forward(q, k, v, causal, window, softcap, scale_val,
                             segment_pos, bk)
    return out, (q, k, v, scale, segment_pos, out, lse)


def _bwd(causal, window, softcap, scale, block_kv, res, dout):
    q, k, v, scale_in, segment_pos, out, lse = res
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    scale_val = float(d ** -0.5) if scale_in is None else float(scale_in)
    bk = min(block_kv, skv)
    nb = skv // bk
    qg, kf, vf, rep = _prep(q, k, v, scale_val)
    kb = kf.reshape(b, nb, bk, hkv, d)
    vb = vf.reshape(b, nb, bk, hkv, d)
    do = jnp.moveaxis(dout.reshape(b, sq, hkv, rep, d), 1, 3) \
        .astype(jnp.float32)                          # (B,G,R,Sq,D)
    og = jnp.moveaxis(out.reshape(b, sq, hkv, rep, d), 1, 3) \
        .astype(jnp.float32)
    delta = jnp.sum(do * og, axis=-1)                 # (B,G,R,Sq)
    if segment_pos is None:
        qpos = jnp.broadcast_to(jnp.arange(sq)[None, :] + (skv - sq), (b, sq))
    else:
        qpos = segment_pos

    def step(dq_acc, blk):
        kblk, vblk, ib = blk
        kpos = ib * bk + jnp.arange(bk)
        s_raw = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kblk)
        if softcap > 0:
            s = jnp.tanh(s_raw / softcap) * softcap
        else:
            s = s_raw
        mask = jnp.ones((b, sq, bk), bool)
        if causal:
            mask &= kpos[None, None, :] <= qpos[:, :, None]
        if window > 0:
            mask &= kpos[None, None, :] > qpos[:, :, None] - window
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])               # (B,G,R,Sq,bk)
        dv = jnp.einsum("bgrqk,bgrqd->bkgd", p, do)
        dp = jnp.einsum("bgrqd,bkgd->bgrqk", do, vblk)
        ds = p * (dp - delta[..., None])
        if softcap > 0:
            # d/dx [softcap * tanh(x/softcap)] = 1 - tanh^2(x/softcap)
            ds = ds * (1.0 - jnp.square(jnp.tanh(s_raw / softcap)))
        ds = jnp.where(mask[:, None, None, :, :], ds, 0.0)
        dk = jnp.einsum("bgrqk,bqgrd->bkgd", ds, qg)  # wrt k (pre-scale q)
        dq_blk = jnp.einsum("bgrqk,bkgd->bqgrd", ds, kblk) * scale_val
        return dq_acc + dq_blk, (dk, dv)

    dq0 = jnp.zeros((b, sq, hkv, rep, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        step, dq0,
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nb)))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, skv, hkv, d)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, skv, hkv, d)
    dq = dq.reshape(b, sq, h, d)
    dseg = None if segment_pos is None else jnp.zeros_like(segment_pos)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dseg)


fused_attention.defvjp(_fwd, _bwd)


def fused_decode_attention(q, k_cache, v_cache, kv_pos, q_pos, *,
                           window: int = 0, softcap: float = 0.0,
                           scale=None):
    """Grouped-einsum decode attention: GQA without materialising
    head-repeated K/V (the XLA-portable twin of the Pallas decode kernel).
    q: (B, H, D); caches (B, C, Hkv, W), W >= D, of which the first D
    lanes are attended; returns (B, H, D)."""
    b, h, d = q.shape
    _, c, hkv, _ = k_cache.shape
    k_cache, v_cache = k_cache[..., :d], v_cache[..., :d]
    rep = h // hkv
    scale_val = float(d ** -0.5) if scale is None else float(scale)
    qg = q.reshape(b, hkv, rep, d).astype(jnp.float32) * scale_val
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, k_cache.astype(jnp.float32))
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    valid = (kv_pos >= 0) & (kv_pos <= q_pos[:, None])
    if window > 0:
        valid &= kv_pos > (q_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def fused_ssd_scan(x, dt, a, b, c, d_skip, initial_state=None,
                   return_final_state=False, chunk: int = 64):
    """Chunked SSD scan in portable JAX — the Pallas ``ssd_scan`` kernel's
    block algorithm expressed as a lax.scan over CHUNKS instead of steps:
    the (B,H,P,N) state round-trips HBM once per chunk (L/chunk times)
    instead of once per token, and the intra-chunk work is three dense
    einsums the MXU likes. Used by the dry-run to model the kernel's
    effect on the memory roofline term (EXPERIMENTS §Perf 'mamba2-ssd').

    Semantics identical to kernels.ref.ssd_scan.
    """
    bsz, L, H, P = x.shape
    _, _, G, N = b.shape
    rep = H // G
    chunk = min(chunk, L)
    if L % chunk != 0:      # fallback: oracle handles ragged lengths
        from repro.kernels import ref as _ref
        return _ref.ssd_scan(x, dt, a, b, c, d_skip,
                             initial_state=initial_state,
                             return_final_state=return_final_state)
    nc = L // chunk
    xf = x.reshape(bsz, nc, chunk, H, P).astype(jnp.float32)
    dtf = dt.reshape(bsz, nc, chunk, H).astype(jnp.float32)
    bh = jnp.repeat(b, rep, axis=2).reshape(bsz, nc, chunk, H, N) \
        .astype(jnp.float32)
    ch = jnp.repeat(c, rep, axis=2).reshape(bsz, nc, chunk, H, N) \
        .astype(jnp.float32)
    af = a.astype(jnp.float32)

    h0 = jnp.zeros((bsz, H, P, N), jnp.float32) if initial_state is None \
        else initial_state.astype(jnp.float32)
    row = jnp.arange(chunk)
    causal = row[:, None] >= row[None, :]

    def step(h_prev, blk):
        xb, dtb, bb, cb = blk                    # (B, Q, H, ...)
        seg = jnp.cumsum(dtb * af, axis=1)       # (B, Q, H)
        # inter-chunk: y_off = exp(seg) * C . h_prev
        y_off = jnp.exp(seg)[..., None] * jnp.einsum(
            "bqhn,bhpn->bqhp", cb, h_prev)
        # intra-chunk: (C B^T ⊙ decay-mask) X
        cbm = jnp.einsum("bqhn,bkhn->bhqk", cb, bb)
        ldec = seg.transpose(0, 2, 1)            # (B, H, Q)
        lmask = jnp.where(causal[None, None],
                          jnp.exp(ldec[:, :, :, None] - ldec[:, :, None, :]),
                          0.0)
        xin = xb * dtb[..., None]                # dt_j * x_j
        y_diag = jnp.einsum("bhqk,bkhp->bqhp", cbm * lmask, xin)
        # state update
        seg_last = seg[:, -1]                    # (B, H)
        w = jnp.exp(seg_last[:, None] - seg)     # (B, Q, H)
        h_new = jnp.exp(seg_last)[..., None, None] * h_prev + jnp.einsum(
            "bqhp,bqhn->bhpn", xin * w[..., None], bb)
        y = y_diag + y_off + xb * d_skip[None, None, :, None]
        return h_new, y

    hf, ys = jax.lax.scan(
        step, h0, (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
                   jnp.moveaxis(bh, 1, 0), jnp.moveaxis(ch, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, L, H, P).astype(x.dtype)
    if return_final_state:
        return y, hf
    return y
