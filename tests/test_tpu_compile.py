"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode checks what a kernel computes, not whether the TPU
compiler accepts it. These tests compile each kernel for one chip of a
described (not attached) v5e at the widths the chip runs: every routing
kernel at single- and multi-block windows, and both attention kernels at
StableLM-3B widths (H=32, D=80). Nothing runs, so no result is checked
here: a compile that passes only rules out what Mosaic refuses. One
whole program is compiled too, the serving engine's decode step, and its
HLO is checked for what the step must not do: copy the KV cache.

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
    slots, max_len = 8, 1024

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(cfg, slots, max_len)))
    ids = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    hlo = engine.jit_decode(cfg).lower(
        params, ids, cache, ids).compile().as_text()
    assert "tpu_custom_call" in hlo

    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        hlo.splitlines()[0])}
    first = len(jax.tree.leaves(params)) + 1      # after params, tokens
    assert aliased == set(range(first, first + len(jax.tree.leaves(cache))))

    kv_tail = cache["blocks"]["layer0"]["k"].shape[-3:]
    slab = slots * math.prod(kv_tail) * 2
    moved = []
    for rows in _instructions(hlo):
        dims_of = {r[0]: r[2] for r in rows}
        for name, dt, dims, op, args in rows:
            if op == "dynamic-update-slice":
                dims = dims_of.get(args[1], ())
            elif op not in ("copy", "copy-done", "dynamic-slice"):
                continue
            if dims[-3:] == kv_tail and math.prod(dims) * 2 >= slab:
                moved.append(f"{name} {op} {dims}")
    assert not moved, moved
