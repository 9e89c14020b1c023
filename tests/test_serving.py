"""Serving engine: slot batching, generation consistency, and the
decode step's in-place cache update."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.kernels import ops
from repro.models import model, transformer
from repro.serving.engine import ServingEngine


def setup():
    cfg = reduced(get_config("stablelm_3b"))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestServingEngine:
    def test_generate_matches_manual_decode(self):
        cfg, params = setup()
        b, s, steps = 4, 16, 4
        prompts = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                     cfg.vocab_size)
        eng = ServingEngine(cfg, params, slots=b, max_len=64)
        out = eng.generate(prompts, steps=steps)
        assert out.tokens.shape == (b, steps)

        # manual: prefill + explicit decode loop
        logits, cache = model.prefill(params, cfg, {"tokens": prompts})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        got = [np.asarray(tok)]
        pos = jnp.full((b,), s, jnp.int32)
        for _ in range(steps - 1):
            logits, cache = model.decode_step(params, cfg, tok, cache, pos)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            pos = pos + 1
            got.append(np.asarray(tok))
        np.testing.assert_array_equal(out.tokens, np.stack(got, 1))

    def test_slot_management(self):
        cfg, params = setup()
        eng = ServingEngine(cfg, params, slots=4, max_len=32)
        assert eng.free_slots() == [0, 1, 2, 3]
        eng.admit(1, first_token=5, start_pos=3)
        assert eng.free_slots() == [0, 2, 3]
        eng.release(1)
        assert eng.free_slots() == [0, 1, 2, 3]

    def test_step_donates_the_cache(self):
        """The decode step takes the cache's buffers for its output: the
        engine's previous cache is consumed, not kept beside a copy."""
        cfg, params = setup()
        eng = ServingEngine(cfg, params, slots=2, max_len=32)
        eng.generate(jnp.ones((2, 8), jnp.int32), steps=2)
        before = jax.tree.leaves(eng.cache)
        eng.step()
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in jax.tree.leaves(eng.cache))

    def test_decode_steps_advance_positions(self):
        cfg, params = setup()
        eng = ServingEngine(cfg, params, slots=2, max_len=32)
        prompts = jnp.ones((2, 8), jnp.int32)
        eng.generate(prompts, steps=2)
        assert int(eng.pos[0]) == 8 + 2 - 1


class TestPartialBatchMerge:
    def test_generate_with_fewer_prompts_than_slots(self):
        """b < slots exercises _merge_batch: the prefilled cache is
        smaller than the engine cache along BOTH the slot and the
        cache-depth axes (regression: the one-axis merge broadcast-failed,
        masked until the py3.10 SyntaxError on this path was fixed)."""
        cfg, params = setup()
        prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                     cfg.vocab_size)
        eng = ServingEngine(cfg, params, slots=4, max_len=64)
        out = eng.generate(prompts, steps=3)
        assert out.tokens.shape == (2, 3)
        assert np.isfinite(out.tokens).all()
        assert list(eng.active[:2]) == [True, True]
        assert eng.free_slots() == [2, 3]

    def test_idle_slots_do_not_leak_into_active_decode(self):
        """Active sequences must decode identically regardless of how
        many idle slots share the batch: idle slots carry kv_pos = -1 and
        must be masked out of attention entirely.

        (Note the b == slots fast path is NOT comparable: it adopts the
        prefill cache directly — an s-deep ring, max_len unused — so it
        attends over a different cache geometry than the merged path.)"""
        cfg, params = setup()
        prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                     cfg.vocab_size)
        four = ServingEngine(cfg, params, slots=4, max_len=64) \
            .generate(prompts, steps=4)
        eight = ServingEngine(cfg, params, slots=8, max_len=64) \
            .generate(prompts, steps=4)
        np.testing.assert_array_equal(four.tokens, eight.tokens)


def _slice_and_write_back_step(params, cfg, tokens, cache, pos):
    """The decode step with every layer's cache sliced out of the stack,
    updated on its own and written back whole: the reference for the
    step that writes attention rows into the stack in place."""
    x = params["embed"][tokens][:, None, :]
    new: dict = {}
    if cfg.n_periods > 0:
        stack = cache["blocks"]
        for i in range(cfg.n_periods):
            for j, kind in enumerate(cfg.layer_pattern):
                name = f"layer{j}"
                p = jax.tree.map(lambda a: a[i], params["blocks"][name])
                c = jax.tree.map(lambda a: a[i], stack[name])
                x, c, _ = transformer._apply_layer_decode(p, cfg, kind, x,
                                                          c, pos)
                stack = {**stack, name: jax.tree.map(
                    lambda a, u: a.at[i].set(u), stack[name], c)}
        new["blocks"] = stack
    if cfg.n_remainder_layers:
        new["remainder"] = []
        for j, p in enumerate(params["remainder"]):
            x, c, _ = transformer._apply_layer_decode(
                p, cfg, cfg.layer_pattern[j], x, cache["remainder"][j], pos)
            new["remainder"].append(c)
    return transformer._logits(params, cfg, x)[:, 0, :], new


# (architecture, layers, kv heads, kernel path): global attention whose
# ring wraps; local + global with a local remainder layer; rglru + local
# with two unrolled rglru remainder layers; phi3's 4 query heads per kv
# head; the Pallas stacked kernel (interpret mode) inside the step; the
# grouped-einsum twin; and mellum2's three sliding layers and one YaRN
# full layer with dropless MoE, on the oracles and on the Pallas kernels
PARITY = [
    ("stablelm_3b", 2, None, "ref"),
    ("gemma2_27b", 5, None, "ref"),
    ("recurrentgemma_2b", 8, None, "ref"),
    ("phi3_medium_14b", 2, 1, "ref"),
    ("stablelm_3b", 2, None, "interp"),
    ("gemma2_27b", 5, None, "interp"),
    ("phi3_medium_14b", 2, 1, "fused"),
    ("mellum2_12b", 4, None, "ref"),
    ("mellum2_12b", 4, None, "interp"),
]


@pytest.mark.parametrize("arch,n_layers,n_kv,impl", PARITY)
def test_in_place_decode_matches_slice_and_write_back(arch, n_layers, n_kv,
                                                       impl, monkeypatch):
    monkeypatch.setattr(ops, "_IMPL", impl)
    cfg = dataclasses.replace(reduced(get_config(arch)), n_layers=n_layers)
    if n_kv is not None:
        cfg = dataclasses.replace(cfg, n_kv_heads=n_kv)
    b, s, max_len, steps = 3, 8, 16, 10
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    keys = jax.random.split(jax.random.PRNGKey(1), steps + 1)
    prompts = jax.random.randint(keys[0], (b, s), 0, cfg.vocab_size)
    _, cache = transformer.prefill(params, cfg, prompts, max_len=max_len)
    want_cache = cache
    # slots at different positions; the last passes max_len and wraps
    pos = s + 3 * jnp.arange(b, dtype=jnp.int32)
    step = jax.jit(lambda p, t, c, q: model.decode_step(p, cfg, t, c, q))
    ref_step = jax.jit(lambda p, t, c, q: _slice_and_write_back_step(
        p, cfg, t, c, q))
    for k in keys[1:]:
        tok = jax.random.randint(k, (b,), 0, cfg.vocab_size)
        logits, cache = step(params, tok, cache, pos)
        want, want_cache = ref_step(params, tok, want_cache, pos)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        jax.tree.map(lambda g, w: np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w)), cache, want_cache)
        pos = pos + 1
    assert int(pos[-1]) > max_len
