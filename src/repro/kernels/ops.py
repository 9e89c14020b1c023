"""jit'd dispatch wrappers for the Pallas kernels.

Every op has three execution paths:

* ``ref``      — the pure-jnp oracle (``repro.kernels.ref``). Default on
                 CPU and for the multi-pod dry-run (fully shardable HLO).
* ``pallas``   — the Pallas TPU kernel compiled for real. Default when
                 JAX's default backend is a TPU.
* ``interp``   — the same Pallas kernel in interpret mode (CPU-correct,
                 used by the kernel test suite).

Select globally via ``set_implementation`` or the REPRO_KERNELS env var,
or per-call via the ``impl=`` keyword; with none of them the platform
decides. There is no fallback: a kernel that does not compile raises.
"""
from __future__ import annotations

import os
from typing import Optional

import jax as _jax

from repro.kernels import ref as _ref

_IMPL: Optional[str] = os.environ.get("REPRO_KERNELS")   # None: by platform
_VALID = ("ref", "pallas", "interp", "fused")


def set_implementation(impl: str) -> None:
    global _IMPL
    if impl not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}, got {impl}")
    _IMPL = impl


def get_implementation() -> str:
    return _resolve(None)


def _resolve(impl: Optional[str]) -> str:
    """Explicit ``impl``, else the global choice, else by platform: the
    compiled kernel on a TPU, the oracle elsewhere."""
    if impl is not None:
        return impl
    if _IMPL is not None:
        return _IMPL
    return "pallas" if _jax.default_backend() == "tpu" else "ref"


def attention(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
              segment_pos=None, impl: Optional[str] = None):
    """Multi-head attention (GQA/window/softcap). See kernels.ref.attention."""
    mode = _resolve(impl)
    if mode == "ref":
        return _ref.attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale,
                              segment_pos=segment_pos)
    if mode == "fused":
        from repro.kernels import fused
        return fused.fused_attention(q, k, v, causal, window, softcap,
                                     scale, segment_pos)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale,
                              segment_pos=segment_pos,
                              interpret=(mode == "interp"))


def decode_attention(q, k_cache, v_cache, kv_pos, q_pos, *, window=0,
                     softcap=0.0, scale=None, layer=None,
                     impl: Optional[str] = None):
    """Single-token attention against a KV cache. See kernels.ref.

    The caches' rows may be wider than q's head dim (zero-padded, see
    ``models.layers.kv_row_width``); only the head dim is attended. With
    ``layer`` (an int32 scalar) the caches are layer-stacked,
    (L, B, C, Hkv, W) and (L, B, C), and the layer ``layer`` is attended:
    the kernel reads it in place, the oracles index it."""
    mode = _resolve(impl)
    if layer is not None and mode in ("pallas", "interp"):
        from repro.kernels import decode_attention as da
        return da.decode_attention_stacked(
            q, k_cache, v_cache, kv_pos, q_pos, layer, window=window,
            softcap=softcap, scale=scale, interpret=(mode == "interp"))
    if layer is not None and mode == "ref":
        return _ref.decode_attention_stacked(
            q, k_cache, v_cache, kv_pos, q_pos, layer, window=window,
            softcap=softcap, scale=scale)
    if layer is not None:
        k_cache, v_cache, kv_pos = (
            _jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for a in (k_cache, v_cache, kv_pos))
    if mode == "ref":
        return _ref.decode_attention(q, k_cache, v_cache, kv_pos, q_pos,
                                     window=window, softcap=softcap,
                                     scale=scale)
    if mode == "fused":
        from repro.kernels import fused
        return fused.fused_decode_attention(q, k_cache, v_cache, kv_pos,
                                            q_pos, window=window,
                                            softcap=softcap, scale=scale)
    from repro.kernels import decode_attention as da
    return da.decode_attention(q, k_cache, v_cache, kv_pos, q_pos,
                               window=window, softcap=softcap, scale=scale,
                               interpret=(mode == "interp"))


def moe_gmm(x, w, group_sizes, layer=None, impl: Optional[str] = None):
    """Grouped matmul of expert-sorted rows, (M, K) x (E, K, N) ->
    (M, N). See kernels.ref.moe_gmm. With ``layer`` (an int32 scalar)
    w is a layer stack (L, E, K, N) and layer ``layer`` is used: the
    kernel reads it in place, the oracle indexes it."""
    mode = _resolve(impl)
    if mode in ("ref", "fused"):
        if layer is not None:
            w = _jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        return _ref.moe_gmm(x, w, group_sizes)
    from repro.kernels import moe_gmm as gmm
    return gmm.moe_gmm(x, w, group_sizes, layer,
                       interpret=(mode == "interp"))


def ssd_scan(x, dt, a, b, c, d_skip, initial_state=None,
             return_final_state=False, impl: Optional[str] = None,
             chunk: int = 64):
    """Mamba-2 SSD scan. See kernels.ref.ssd_scan."""
    mode = _resolve(impl)
    if mode == "ref":
        return _ref.ssd_scan(x, dt, a, b, c, d_skip,
                             initial_state=initial_state,
                             return_final_state=return_final_state)
    if mode == "fused":
        from repro.kernels import fused
        return fused.fused_ssd_scan(x, dt, a, b, c, d_skip,
                                    initial_state=initial_state,
                                    return_final_state=return_final_state,
                                    chunk=chunk)
    from repro.kernels import ssd_scan as ssd
    return ssd.ssd_scan(x, dt, a, b, c, d_skip,
                        initial_state=initial_state,
                        return_final_state=return_final_state,
                        chunk=chunk, interpret=(mode == "interp"))


def routing_score(lam, alpha, beta, gamma, mu, n, rtt, slo, cost,
                  erlang_c_table, impl: Optional[str] = None,
                  block_r: int = 256):
    """Batched LA-IMR routing decisions. See kernels.ref.routing_score."""
    mode = _resolve(impl)
    if mode in ("ref", "fused"):
        return _jit_ref_routing_score(lam, alpha, beta, gamma, mu, n, rtt,
                                      slo, cost, erlang_c_table)
    from repro.kernels import routing_score as rs
    return rs.routing_score(lam, alpha, beta, gamma, mu, n, rtt, slo, cost,
                            erlang_c_table, block_r=block_r,
                            interpret=(mode == "interp"))


def routing_guard(lam, alpha, beta, gamma, mu, n, rtt, tau, home, up,
                  erlang_c_table, impl: Optional[str] = None,
                  block_r: int = 256):
    """Fused Algorithm-1 guarded routing. See kernels.ref.routing_guard."""
    mode = _resolve(impl)
    if mode in ("ref", "fused"):
        return _jit_ref_routing_guard(lam, alpha, beta, gamma, mu, n, rtt,
                                      tau, home, up, erlang_c_table)
    from repro.kernels import routing_decide as rd
    return rd.routing_guard(lam, alpha, beta, gamma, mu, n, rtt, tau, home,
                            up, erlang_c_table, block_r=block_r,
                            interpret=(mode == "interp"))


def routing_topk(lam, alpha, beta, gamma, mu, n, rtt, slo, cost,
                 erlang_c_table, k: int = 2, margin: float = 0.0,
                 impl: Optional[str] = None, block_r: int = 256):
    """Fused top-k feasible select. See kernels.ref.routing_topk."""
    mode = _resolve(impl)
    if mode in ("ref", "fused"):
        return _jit_ref_routing_topk(lam, alpha, beta, gamma, mu, n, rtt,
                                     slo, cost, erlang_c_table, k=k,
                                     margin=margin)
    from repro.kernels import routing_decide as rd
    return rd.routing_topk(lam, alpha, beta, gamma, mu, n, rtt, slo, cost,
                           erlang_c_table, k=k, margin=margin,
                           block_r=block_r, interpret=(mode == "interp"))


def routing_attain(lam, alpha, beta, gamma, mu, n, rtt, slo, sigma, avail,
                   erlang_c_table, k: int = 2, margin: float = 0.0,
                   impl: Optional[str] = None, block_r: int = 256):
    """Fused attainment-argmax select. See kernels.ref.routing_attain."""
    mode = _resolve(impl)
    if mode in ("ref", "fused"):
        return _jit_ref_routing_attain(lam, alpha, beta, gamma, mu, n, rtt,
                                       slo, sigma, avail, erlang_c_table,
                                       k=k, margin=margin)
    from repro.kernels import routing_decide as rd
    return rd.routing_attain(lam, alpha, beta, gamma, mu, n, rtt, slo,
                             sigma, avail, erlang_c_table, k=k,
                             margin=margin, block_r=block_r,
                             interpret=(mode == "interp"))


# jitted oracle paths: the routing ops sit on the per-window hot path of
# the control plane, where retracing the pure-jnp oracle per flush would
# dominate the decision cost. k/margin are static (they shape the
# outputs); array shapes are bucketed by the caller (pow2 padding).
_jit_ref_routing_score = _jax.jit(_ref.routing_score)
_jit_ref_routing_guard = _jax.jit(_ref.routing_guard)
_jit_ref_routing_topk = _jax.jit(_ref.routing_topk,
                                 static_argnames=("k", "margin"))
_jit_ref_routing_attain = _jax.jit(_ref.routing_attain,
                                   static_argnames=("k", "margin"))
