"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode checks what a kernel computes, not whether the TPU
compiler accepts it. These tests compile each kernel for one chip of a
described (not attached) v5e at the widths the chip runs: every routing
kernel at single- and multi-block windows, both attention kernels at
StableLM-3B widths (H=32, D=80), the stacked decode kernel at
Mellum2-12B widths (H=32, Hkv=4, D=128, window 1024) and at the query
groups of DBRX, Phi-3 and Arctic (6, 4 and 7 heads a KV head), the grouped
expert matmul at Mellum2's expert widths (d=2304, f=896) at the row
counts of its prefill and decode. Nothing runs, so no result is checked
here: a compile that passes only rules out what Mosaic refuses. Whole
programs are compiled too, the serving engine's decode step of both
models, and their HLO is checked for what the step must not do: copy
the KV cache, or copy a layer's experts out of their stack.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler's library, and every test
worker imports this file.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm as gmm
from repro.kernels import ops
from repro.kernels import routing_decide as rd
from repro.kernels import routing_score as rs
from repro.models import model
from repro.serving import engine

T = 65          # AdmissionConfig.erlang_table_size


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo


def _routing_call(kernel, i, r, block_r):
    """(fn, argument shapes in sharding-less form) for one routing kernel
    at a window of r requests over i candidates, as the policies call
    it: (R, I) rates and SLO rows, (I,) candidate columns."""
    f32, i32 = jnp.float32, jnp.int32
    cand = [((i,), f32)] * 6
    rows = ((r, i), f32)
    table = ((i, T), f32)
    if kernel == "score":
        return (functools.partial(rs.routing_score, block_r=block_r),
                [rows, *cand, rows, ((i,), f32), table])
    if kernel == "guard":
        return (functools.partial(rd.routing_guard, block_r=block_r),
                [rows, *cand, ((r,), f32), ((r,), i32), ((r,), i32), table])
    if kernel == "topk":
        return (functools.partial(rd.routing_topk, k=2, margin=0.1,
                                  block_r=block_r),
                [rows, *cand, rows, ((i,), f32), table])
    return (functools.partial(rd.routing_attain, k=2, margin=0.1,
                              block_r=block_r),
            [rows, *cand, rows, ((i,), f32), ((i,), f32), table])


# (candidates, window rows, block_r): the block sizes pow2 padding gives
# (8, 64, 256) as single blocks, then windows of several blocks, then the
# 32-candidate fleet at the largest block
WINDOWS = [(4, 8, 8), (4, 64, 64), (4, 256, 256),
           (4, 16, 8), (4, 256, 64), (4, 512, 256), (32, 256, 256)]


@pytest.mark.parametrize("i,r,block_r", WINDOWS)
@pytest.mark.parametrize("kernel", ["score", "guard", "topk", "attain"])
def test_routing_kernel_compiles(one_chip, kernel, i, r, block_r):
    fn, shapes = _routing_call(kernel, i, r, block_r)
    _compile(fn, *[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                   for s, d in shapes])


def test_routing_score_shared_rate_and_slo_compile(one_chip):
    """The (R,) shared-rate / (I,) shared-SLO form of routing_score."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(functools.partial(rs.routing_score, block_r=64),
             s((256,), jnp.float32), *[s((4,), jnp.float32)] * 8,
             s((4, T), jnp.float32))


@pytest.mark.parametrize("b,s", [(8, 128), (1, 256)])
def test_flash_attention_compiles_at_stablelm_width(one_chip, b, s):
    x = jax.ShapeDtypeStruct((b, s, 32, 80), jnp.bfloat16, sharding=one_chip)
    _compile(fa.flash_attention, x, x, x)


@pytest.mark.parametrize("c", [128, 1024])
def test_decode_attention_compiles_at_stablelm_width(one_chip, c):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(da.decode_attention, s((8, 32, 80), jnp.bfloat16),
             s((8, c, 32, 80), jnp.bfloat16), s((8, c, 32, 80), jnp.bfloat16),
             s((8, c), jnp.int32), s((8,), jnp.int32))


@pytest.mark.parametrize("c", [128, 1024])
def test_stacked_decode_attention_compiles_at_stablelm_width(one_chip, c):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(da.decode_attention_stacked, s((8, 32, 80), jnp.bfloat16),
             s((32, 8, c, 32, 80), jnp.bfloat16),
             s((32, 8, c, 32, 80), jnp.bfloat16), s((32, 8, c), jnp.int32),
             s((8,), jnp.int32), s((), jnp.int32))


@pytest.mark.parametrize("c,window", [(1024, 1024), (4096, 0)])
def test_stacked_decode_attention_compiles_at_mellum2_width(one_chip, c,
                                                            window):
    """GQA with 8 query heads per KV head, D 128 (no lane padding): the
    sliding layers' 1024-slot ring and the full layers' 4096 positions."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(functools.partial(da.decode_attention_stacked, window=window),
             s((8, 32, 128), jnp.bfloat16),
             s((9, 8, c, 4, 128), jnp.bfloat16),
             s((9, 8, c, 4, 128), jnp.bfloat16), s((9, 8, c), jnp.int32),
             s((8,), jnp.int32), s((), jnp.int32))


@pytest.mark.parametrize("h,hkv", [(48, 8), (40, 10), (56, 8)])
def test_stacked_decode_attention_compiles_at_gqa_width(one_chip, h, hkv):
    """Query groups that do not fill a sublane tile: DBRX (rep 6), Phi-3
    (rep 4) and Arctic (rep 7), D 128 over 4096 positions."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(da.decode_attention_stacked, s((8, h, 128), jnp.bfloat16),
             s((4, 8, 4096, hkv, 128), jnp.bfloat16),
             s((4, 8, 4096, hkv, 128), jnp.bfloat16),
             s((4, 8, 4096), jnp.int32), s((8,), jnp.int32),
             s((), jnp.int32))


# rows: a prefill wave of 7 x 2048 tokens, top-8; a decode step of 7
# requests and of the engine's 8 slots
@pytest.mark.parametrize("rows", [14336 * 8, 56, 64])
@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)])
def test_moe_gmm_compiles_at_mellum2_width(one_chip, rows, k, n):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(gmm.moe_gmm, s((rows, k), jnp.bfloat16),
             s((64, k, n), jnp.bfloat16), s((64,), jnp.int32))


def test_moe_gmm_compiles_on_a_layer_stack(one_chip):
    """The decode step's form: one layer's experts of a (3, E, d, f)
    stack, the layer index a scalar-prefetch operand."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(gmm.moe_gmm, s((64, 2304), jnp.bfloat16),
             s((3, 64, 2304, 896), jnp.bfloat16), s((64,), jnp.int32),
             s((), jnp.int32))


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(([^)]*)\)")


def _instructions(hlo: str):
    """(name, dtype, dims, opcode, operand names) of each array-valued
    instruction, computation by computation (names are per computation)."""
    comps = re.split(r"\n(?=\S)", hlo)
    for comp in comps:
        rows = []
        for line in comp.splitlines():
            m = _INSTR.match(line)
            if m:
                name, dt, dims, op, args = m.groups()
                rows.append((name, dt, tuple(int(d) for d in dims.split(",")
                                             if d), op,
                             re.findall(r"%([\w.\-]+)", args)))
        yield rows


def test_served_decode_updates_the_cache_in_place(one_chip, monkeypatch):
    """``ServingEngine``'s decode step at StableLM-3B widths (2 layers, 8
    slots, 1024 positions, head dim 80, bf16) on one v5e, its cache in
    the chip's own layouts: every cache leaf is aliased from input to
    output, and no copy, dynamic-slice or dynamic-update-slice moves a K/V
    buffer as large as one layer's slab."""
    monkeypatch.setattr(ops, "_IMPL", "pallas")
    cfg = dataclasses.replace(get_config("stablelm_3b"), n_layers=2)
    hlo, params, cache = _compiled_decode(one_chip, cfg, 8, 1024)
    _assert_cache_in_place(hlo, params, cache, 8)


def test_served_moe_decode_reads_experts_in_place(one_chip, monkeypatch):
    """The decode step at Mellum2-12B widths (two periods of three
    sliding layers and one full, 64 experts of 2304 x 896, 8 slots, 4096
    positions): the cache and the MoE counters are aliased from input
    to output, no K/V slab is relaid or sliced, and no layer's expert
    stack is copied or sliced out (``moe_gmm`` reads it where it lies).

    The sliding layers' rings (16.8 MB a stack here) are small enough
    that the compiler's memory-space assignment prefetches some into
    VMEM and evicts them back, as asynchronous copy-start/copy-done
    pairs; those are its choice of where the ring lives for the step,
    not a relayout, and are not counted here."""
    monkeypatch.setattr(ops, "_IMPL", "pallas")
    cfg = dataclasses.replace(get_config("mellum2_12b"), n_layers=8)
    hlo, params, cache = _compiled_decode(one_chip, cfg, 8, 4096)
    _assert_cache_in_place(hlo, params, cache, 8, extra_donated=1,
                           moves=("copy", "dynamic-slice"))
    # every moe_gmm call's weight operand is a whole (periods, E, K, N)
    # stack, never a layer's slice of it
    # (operands: six scalar-prefetch vectors, the rows, the weights)
    weights = [re.findall(r"bf16\[([\d,]+)\]", line.split(
        "operand_layout_constraints={", 1)[1])[1]
        for line in hlo.splitlines()
        if re.match(r"\s*%moe_gmm[.\d]* = .*custom-call\(", line)]
    assert weights
    for w in weights:
        stack = tuple(int(d) for d in w.split(","))
        assert stack[:2] == (cfg.n_periods, cfg.n_experts), w


def _compiled_decode(one_chip, cfg, slots, max_len):
    """(HLO text, parameter and cache shapes) of ServingEngine's decode
    step compiled for one described v5e."""
    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(cfg, slots, max_len)))
    ids = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    counts = model.init_moe_counts(cfg)
    args = (params, ids, cache, ids)
    if counts is not None:
        args += (on_chip(jax.eval_shape(lambda: counts)),)
    hlo = engine.jit_decode(cfg).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo, params, cache


def _assert_cache_in_place(hlo, params, cache, slots, extra_donated=0,
                           moves=("copy", "copy-done", "dynamic-slice")):
    """Every cache leaf (and the ``extra_donated`` leaves after the
    position vector) aliased from input to output; no ``moves``
    operation or dynamic-update-slice moves a K/V buffer as large as
    one layer's slab."""
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        hlo.splitlines()[0])}
    first = len(jax.tree.leaves(params)) + 1      # after params, tokens
    leaves = len(jax.tree.leaves(cache))
    after = first + leaves + 1                    # after the positions
    assert aliased == (set(range(first, first + leaves))
                       | set(range(after, after + extra_donated)))

    kv_tail = cache["blocks"]["layer0"]["k"].shape[-3:]
    slab = slots * math.prod(kv_tail) * 2
    moved = []
    for rows in _instructions(hlo):
        dims_of = {r[0]: r[2] for r in rows}
        for name, dt, dims, op, args in rows:
            if op == "dynamic-update-slice":
                dims = dims_of.get(args[1], ())
            elif op not in moves:
                continue
            if dims[-3:] == kv_tail and math.prod(dims) * 2 >= slab:
                moved.append(f"{name} {op} {dims}")
    assert not moved, moved
