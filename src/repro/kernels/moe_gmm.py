"""Grouped matmul over experts (dropless MoE expert FFN) for TPU.

``moe_gmm(x, w, group_sizes)``: the rows of ``x`` (M, K) arrive sorted
by expert, ``group_sizes[e]`` of them for expert e, and row r of the
result is ``x[r] @ w[e(r)]``. No row is dropped and no expert has a
capacity: the groups are as large as the router made them.

The grid walks *visits*: (row tile, expert) pairs whose intersection
holds rows, in row order. A row tile that two experts share is visited
once for each; an expert that received no row is never visited, so its
weights are never read. The visit list is computed on the device from
the group sizes and handed to the kernel as scalar-prefetch operands,
which the index maps read: consecutive visits of one expert keep its
weight block in VMEM (the pipeline fetches a block only when its index
changes), and a decode step with its few dozen rows streams exactly the
experts they were routed to. The list has the static length
``tiles + E`` (an upper bound); visits past the real count repeat the
last one and compute nothing.

The weights may be a stack of layers, (L, E, K, N), with a layer index:
the index is another scalar-prefetch operand and the index map picks
the layer's expert blocks where they lie, so a decode step that scans
over layers never slices a layer's experts out of the stack (a Mosaic
kernel's operand is a buffer of its own: a slice would be a copy of
every expert, read and written each step).

Each visit multiplies its row tile by the whole (K, tn) weight block in
one MXU dot with f32 accumulation and writes only the rows of its own
group into the output tile, which stays resident in VMEM across the
consecutive visits of that tile and is zeroed at the first. Rows past
``sum(group_sizes)`` come out zero.

Oracle: ``repro.kernels.ref.moe_gmm``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tile_ref, group_ref, widx_ref, offs_ref, nvis_ref, layer_ref,
            x_ref, w_ref, o_ref, *, block_m: int, n_groups: int):
    del widx_ref, layer_ref             # read by the weight's index map
    v = pl.program_id(1)
    tile = tile_ref[v]
    group = group_ref[v]

    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((v < nvis_ref[0]) & (group < n_groups))
    def _visit():
        rows = tile * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        mine = (rows >= offs_ref[group]) & (rows < offs_ref[group + 1])
        y = jax.lax.dot_general(x_ref[...], w_ref[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _visits(group_sizes: jax.Array, m_pad: int, block_m: int):
    """The visit list for rows padded to ``m_pad``: per visit its row
    tile, its group (E for the zero tail past the last group) and the
    expert whose weights it reads; the group offsets (E + 1,); the
    number of real visits (1,)."""
    e = group_sizes.shape[0]
    n_vis = m_pad // block_m + e
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    total = ends[-1]
    # groups 0..E-1, then the tail [total, m_pad) as group E
    lo = jnp.append(starts, total)
    hi = jnp.append(ends, m_pad)
    count = jnp.where(hi > lo, (hi - 1) // block_m - lo // block_m + 1, 0)
    vis_end = jnp.cumsum(count)
    n_real = vis_end[-1]
    v = jnp.minimum(jnp.arange(n_vis, dtype=jnp.int32), n_real - 1)
    group = jnp.sum(v[:, None] >= vis_end[None, :], axis=1,
                    dtype=jnp.int32)
    tile = lo[group] // block_m + v - (vis_end - count)[group]
    # the tail reads the weights the visit before it read
    last = jnp.max(jnp.where(group_sizes > 0, jnp.arange(e), 0))
    widx = jnp.where(group < e, group, last).astype(jnp.int32)
    offsets = jnp.append(jnp.zeros((1,), jnp.int32), ends)
    return (tile.astype(jnp.int32), group, widx, offsets,
            jnp.reshape(n_real, (1,)).astype(jnp.int32))


def _block_n(k: int, n: int, itemsize: int, budget: int = 8 << 20) -> int:
    """The widest lane block of the (K, N) weight that divides N and
    keeps one weight block within ``budget`` bytes."""
    if k * n * itemsize <= budget or n % 128:
        return n
    best = 128
    for tn in range(128, n, 128):
        if n % tn == 0 and k * tn * itemsize <= budget:
            best = tn
    return best


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def moe_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
            layer: Optional[jax.Array] = None, *, block_m: int = 512,
            interpret: bool = False) -> jax.Array:
    """x: (M, K) rows sorted by group; w: (E, K, N), or with ``layer``
    (an int32 scalar) a stack (L, E, K, N) of which that layer's experts
    are read; group_sizes: (E,) int32. Returns (M, N) in x's dtype."""
    if layer is None:
        w, layer = w[None], 0
    m, k = x.shape
    _, e, _, n = w.shape
    block_m = min(block_m, -(-m // 16) * 16)
    m_pad = -(-m // block_m) * block_m
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    tn = _block_n(k, n, w.dtype.itemsize)
    tile, group, widx, offsets, n_real = _visits(group_sizes, m_pad, block_m)
    grid = (n // tn, tile.shape[0])
    kernel = functools.partial(_kernel, block_m=block_m, n_groups=e)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k),
                         lambda j, v, t, g, wi, o, nv, ly: (t[v], 0)),
            pl.BlockSpec((None, None, k, tn),
                         lambda j, v, t, g, wi, o, nv, ly: (ly[0], wi[v], 0,
                                                            j)),
        ],
        out_specs=pl.BlockSpec((block_m, tn),
                               lambda j, v, t, g, wi, o, nv, ly: (t[v], j)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        # a (2304, 896) bf16 weight block and a 512-row tile, each
        # double-buffered, with the f32 product: ~20 MB, over the 16 MB
        # default scoped VMEM
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="moe_gmm",
    )(tile, group, widx, offsets, n_real,
      jnp.reshape(layer, (1,)).astype(jnp.int32), x, w)
    return out[:m]
