"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode checks what a kernel computes, not whether the TPU
compiler accepts it. These tests compile each kernel for one chip of a
described (not attached) v5e at the widths the chip runs: every routing
kernel at single- and multi-block windows, and both attention kernels at
StableLM-3B widths (H=32, D=80). Nothing runs, so no result is checked
here: a compile that passes only rules out what Mosaic refuses.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler's library, and every test
worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import routing_decide as rd
from repro.kernels import routing_score as rs

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
