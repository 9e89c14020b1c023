"""Sharding rules (PartitionSpec construction) + HLO cost analysis.

Uses AbstractMesh so the 16x16 production topology can be reasoned about
without 256 devices; the dry-run exercises the real thing.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding
from repro.launch import hlo_analysis


def mesh16():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh_multipod():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestParamSpec:
    def test_attention_heads_divisible(self):
        # 96 q heads on 16-way model axis -> head-sharded column parallel
        s = sharding.param_spec("blocks/layer0/attn/wq", (12, 18432, 96, 192),
                                mesh16(), fsdp=True)
        assert s == P(None, ("data",), "model", None)

    def test_attention_heads_not_divisible_falls_back(self):
        # 40 heads (phi3) -> keep d_model sharding only, never crash
        s = sharding.param_spec("blocks/layer0/attn/wq", (40, 5120, 40, 128),
                                mesh16(), fsdp=True)
        assert s == P(None, ("data",), None, None)

    def test_kv_heads_replicated_when_small(self):
        s = sharding.param_spec("blocks/layer0/attn/wk", (48, 6144, 8, 128),
                                mesh16(), fsdp=True)
        assert s[2] is None       # 8 kv heads !% 16

    def test_mlp(self):
        s = sharding.param_spec("blocks/layer0/mlp/wi", (23, 4608, 36864),
                                mesh16(), fsdp=True)
        assert s == P(None, ("data",), "model")
        s = sharding.param_spec("blocks/layer0/mlp/wo", (23, 36864, 4608),
                                mesh16(), fsdp=True)
        assert s == P(None, "model", ("data",))

    def test_moe_expert_parallel(self):
        s = sharding.param_spec("blocks/layer0/moe/wi", (40, 16, 6144, 10752),
                                mesh16(), fsdp=True)
        assert s == P(None, "model", ("data",), None)

    def test_embed_vocab_sharding_guard(self):
        ok = sharding.param_spec("embed", (256000, 4608), mesh16(), fsdp=True)
        assert ok == P("model", ("data",))
        # whisper vocab 51865 is not divisible by 16 -> replicated dim
        bad = sharding.param_spec("embed", (51865, 768), mesh16(), fsdp=True)
        assert bad == P(None, ("data",))

    def test_serve_mode_disables_fsdp(self):
        s = sharding.param_spec("blocks/layer0/mlp/wi", (23, 4608, 36864),
                                mesh16(), fsdp=False)
        assert s == P(None, None, "model")

    def test_multipod_fsdp_uses_pod_axis(self):
        s = sharding.param_spec("blocks/layer0/mlp/wi", (23, 4608, 36864),
                                mesh_multipod(), fsdp=True)
        assert s == P(None, ("pod", "data"), "model")

    def test_norms_replicated(self):
        s = sharding.param_spec("blocks/layer0/norm1/scale", (12, 4608),
                                mesh16(), fsdp=True)
        assert s == P(None, None)


class TestCacheSpec:
    def test_kv_heads_over_model(self):
        # gemma2: 16 kv heads divide the model axis
        s = sharding.cache_spec("blocks/layer0/k", (23, 128, 32768, 16, 128),
                                mesh16(), None, long_context=False)
        assert s == P(None, ("data",), None, "model", None)

    def test_kv_seq_fallback(self):
        # 8 kv heads don't divide -> shard cache length over model
        s = sharding.cache_spec("blocks/layer0/k", (48, 128, 32768, 8, 128),
                                mesh16(), None, long_context=False)
        assert s == P(None, ("data",), "model", None, None)

    def test_long_context_shards_sequence_over_data(self):
        s = sharding.cache_spec("blocks/layer0/k", (23, 1, 524288, 16, 128),
                                mesh16(), None, long_context=True)
        assert s == P(None, None, "data", "model", None)

    def test_ssm_state(self):
        s = sharding.cache_spec("blocks/layer0/ssm", (48, 128, 32, 64, 128),
                                mesh16(), None, long_context=False)
        assert s == P(None, ("data",), "model", None, None)

    def test_whisper_cross_cache_has_layer_axis(self):
        s = sharding.cache_spec("cross_k", (12, 128, 32768, 12, 64),
                                mesh16(), None, long_context=False)
        # leading layer axis unsharded; 12 heads !% 16 -> seq over model
        assert s == P(None, ("data",), "model", None, None)


class TestActivationConstraint:
    def test_identity_outside_context(self):
        x = jnp.ones((4, 8))
        assert sharding.constrain_batch(x) is x

    def test_constraint_set_and_cleared(self):
        sharding.set_activation_batch_axes(("data",))
        try:
            # outside jit/mesh this still traces fine under jit with a mesh
            assert sharding._ACT_BATCH_AXES == ("data",)
        finally:
            sharding.set_activation_batch_axes(None)
        x = jnp.ones((4, 8))
        assert sharding.constrain_batch(x) is x


class TestHloAnalysis:
    def test_dot_flops_exact(self):
        @jax.jit
        def f(a, b):
            return a @ b
        m, k, n = 64, 128, 32
        txt = f.lower(jnp.ones((m, k)), jnp.ones((k, n))).compile().as_text()
        c = hlo_analysis.analyze(txt)
        assert c.flops == pytest.approx(2 * m * k * n, rel=0.01)

    def test_scan_trip_count_scaling(self):
        def body(x, _):
            return x @ x, None

        @jax.jit
        def f(x):
            y, _ = jax.lax.scan(body, x, None, length=7)
            return y
        txt = f.lower(jnp.ones((32, 32))).compile().as_text()
        c = hlo_analysis.analyze(txt)
        assert c.flops == pytest.approx(7 * 2 * 32**3, rel=0.05)

    def test_deeper_scan_scales_linearly(self):
        def make(n):
            def body(x, _):
                return x @ x, None

            @jax.jit
            def f(x):
                y, _ = jax.lax.scan(body, x, None, length=n)
                return y
            return f.lower(jnp.ones((16, 16))).compile().as_text()
        c2 = hlo_analysis.analyze(make(2)).flops
        c8 = hlo_analysis.analyze(make(8)).flops
        assert c8 == pytest.approx(4 * c2, rel=0.05)

    def test_bytes_positive_and_collectives_empty_on_1dev(self):
        @jax.jit
        def f(a):
            return jnp.tanh(a) * 2.0
        txt = f.lower(jnp.ones((128, 128))).compile().as_text()
        c = hlo_analysis.analyze(txt)
        assert c.bytes > 0
        assert c.collectives == {}

    def test_type_bytes_parser(self):
        assert hlo_analysis._type_bytes("bf16[4,8]{1,0}") == 64
        assert hlo_analysis._type_bytes("(f32[2]{0}, s32[3]{0})") == 20
        assert hlo_analysis._type_bytes("pred[7]") == 7
