"""Mellum2 on the serving path, at small sizes on the CPU: the dropless
MoE against a per-token loop, YaRN rotary frequencies against the formula written out, and a
reduced Mellum2 prefilled and decoded through ``ServingEngine`` against
the plain reference of the chip benchmark (``bench/reference/mellum2``)
on logits, with the engine's MoE counters."""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights_moe
from bench.reference import mellum2
from bench.systems import serve_moe
from repro.configs.base import Yarn, get_config
from repro.kernels import ops
from repro.models import layers, model
from repro.serving.engine import ServingEngine

CONF = Path(__file__).resolve().parents[1] / "bench/configs/mellum2_moe.json"


# ------------------------------------------------------- dropless MoE
def _per_token_moe(p, x, top_k):
    """The MoE one token at a time: softmax router, top-k renormalised,
    the gate-weighted sum of the chosen experts' SwiGLU outputs."""
    out = []
    for t in np.asarray(x, np.float64):
        probs = np.exp(t @ np.asarray(p["router"], np.float64))
        probs /= probs.sum()
        top = np.argsort(-probs)[:top_k]
        gates = probs[top] / probs[top].sum()
        y = 0.0
        for e, g in zip(top, gates):
            wg, wi, wo = (np.asarray(p[n][e], np.float64)
                          for n in ("wg", "wi", "wo"))
            a = t @ wg
            y = y + g * ((a / (1 + np.exp(-a)) * (t @ wi)) @ wo)
        out.append(y)
    return np.stack(out)


@pytest.mark.parametrize("impl", ["ref", "interp"])
def test_dropless_moe_matches_per_token_loop(impl, monkeypatch):
    """8 experts, top-4, 3 tokens: the capacity path keeps
    round(3 * 4 / 8 * 1.25) = 2 rows an expert and drops the rest at
    this routing; the serving path drops none."""
    monkeypatch.setattr(ops, "_IMPL", impl)
    e, k, d, f = 8, 4, 32, 64
    p = layers.moe_init(jax.random.PRNGKey(3), d, f, e, "swiglu",
                        jnp.float32)
    p["router"] = p["router"] * 4.0        # uneven routing
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 3, d), jnp.float32)
    probs = jax.nn.softmax(x[0] @ p["router"], axis=-1)
    chosen = np.asarray(jax.lax.top_k(probs, k)[1]).reshape(-1)
    assert np.bincount(chosen, minlength=e).max() > 2   # capacity drops

    y, stats = layers.moe_dropless(p, x, top_k=k, kind="swiglu")
    # f32 on both sides (the loop in f64): summation order only
    np.testing.assert_allclose(np.asarray(y[0]), _per_token_moe(p, x[0], k),
                               rtol=1e-4, atol=1e-5)
    assert stats.tolist() == [len(set(chosen.tolist())), 3 * k]


# --------------------------------------------------------------- YaRN
def test_yarn_rope_matches_formula():
    """The full layers' rotary embedding: frequencies theta^(-2i/d)
    blended with themselves over ``factor`` along the linear ramp
    between floor(dim(beta_fast)) and ceil(dim(beta_slow)), where
    dim(r) = d ln(L / (2 pi r)) / (2 ln theta); cos and sin scaled by
    the attention factor; pairs (i, i + d/2) rotated."""
    d, theta = 128, 500000.0
    yarn = get_config("mellum2_12b").global_yarn
    pos = np.arange(0, 6000, 37)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                     (len(pos), 2, d), jnp.float32))

    def dim(r):
        return d * math.log(yarn.original_max_positions / (2 * math.pi * r)) \
            / (2 * math.log(theta))
    lo, hi = math.floor(dim(yarn.beta_fast)), math.ceil(dim(yarn.beta_slow))
    assert 0 < lo < hi < d // 2
    i = np.arange(d // 2)
    base = theta ** (-2.0 * i / d)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    freq = base * (1 - ramp) + base / yarn.factor * ramp
    ang = pos[:, None, None] * freq
    m = yarn.attention_factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    want = np.concatenate([x1 * np.cos(ang) * m - x2 * np.sin(ang) * m,
                           x2 * np.cos(ang) * m + x1 * np.sin(ang) * m], -1)
    got = layers.rope(jnp.asarray(x), jnp.asarray(pos), theta, yarn)
    # f32 angles up to 6000 rad: about 6000 * 2^-24 of phase error
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)
    assert float(yarn.attention_factor) == pytest.approx(
        0.1 * math.log(yarn.factor) + 1.0)
    # without YaRN the frequencies are the plain ones, unscaled
    plain, scale = layers.rope_frequencies(d, theta)
    np.testing.assert_allclose(np.asarray(plain), base, rtol=1e-6)
    assert scale == 1.0


def test_yarn_is_on_the_full_layers_only():
    cfg = get_config("mellum2_12b")
    from repro.models import transformer
    assert transformer.attn_spec(cfg, "attn").yarn == cfg.global_yarn
    assert transformer.attn_spec(cfg, "local").yarn is None
    assert transformer.attn_spec(cfg, "local").window == 1024
    assert isinstance(cfg.global_yarn, Yarn)


# ------------------------------------------- the model through the engine
def small_conf(**over) -> dict:
    """The benchmark's Mellum2 file at a small size: 2 periods, 4 query
    and 2 KV heads of 32, 8 experts top-4 of width 64, window 8."""
    conf = json.loads(CONF.read_text())
    conf.update(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, num_experts=8,
                num_experts_per_tok=4, moe_intermediate_size=64,
                num_hidden_layers=8, sliding_window=8, vocab_size=256,
                torch_dtype="float32")
    conf.update(over)
    return conf


def test_benchmark_file_maps_to_the_registered_config():
    conf = json.loads(CONF.read_text())
    got = serve_moe.arch_config(conf)
    want = get_config("mellum2_12b")
    fields = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "layer_pattern", "window", "n_experts", "top_k",
              "mlp_kind", "norm", "rope_theta", "global_yarn",
              "tie_embeddings")
    assert {f: getattr(got, f) for f in fields} == \
        {f: getattr(want, f) for f in fields}
    assert got.n_layers == 12 and want.n_layers == 28


@pytest.mark.parametrize("impl", ["ref", "interp"])
def test_served_logits_match_reference(impl, monkeypatch):
    """Three 12-token prompts (longer than the window, so the sliding
    layers' prefill ring keeps only the last 8) prefilled and decoded 6
    steps through ServingEngine (4 slots, 32 positions), each logit row
    the engine computed against the reference's forward pass over the
    same tokens."""
    monkeypatch.setattr(ops, "_IMPL", impl)
    conf = small_conf()
    cfg = serve_moe.arch_config(conf)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights_moe.make(conf, 11))
    b, s, steps = 3, 12, 6
    prompts = jax.random.randint(jax.random.PRNGKey(6), (b, s), 0,
                                 conf["vocab_size"])
    eng = ServingEngine(cfg, params, slots=4, max_len=32)
    seen = []
    for name in ("_prefill", "_decode"):
        fn = getattr(eng, name)

        def spy(*a, fn=fn):
            out = fn(*a)
            seen.append(np.asarray(out[0])[:b])
            return out
        setattr(eng, name, spy)
    out = eng.generate(prompts, steps=steps)
    got = np.stack(seen, axis=1)                        # (b, steps, V)

    seq = np.concatenate([np.asarray(prompts), out.tokens[:, :-1]], 1)
    want = np.asarray(mellum2.logits(params, conf, seq))[:, s - 1:]
    # f32 on both sides; they differ in summation order and in how the
    # rotary angles and the softmax are formed: ~3e-6 on logits of up to
    # ~4, a tenth of the tolerance. The same program in bf16 misses it
    # by over a thousand times.
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    counts = eng.moe_counters()
    assert counts.shape == (8, 2)
    # every slot routes top_k rows in every layer at every decode step
    assert (counts[:, 1] == (steps - 1) * 4 * 4).all()
    assert ((counts[:, 0] >= steps - 1)
            & (counts[:, 0] <= (steps - 1) * 8)).all()


def test_dense_engine_keeps_no_moe_counters():
    cfg = dataclasses.replace(get_config("stablelm_3b"), n_layers=1,
                              d_model=64, n_heads=2, n_kv_heads=2,
                              head_dim=32, d_ff=64, vocab_size=64,
                              dtype="float32")
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, slots=2, max_len=8)
    eng.generate(jnp.ones((2, 4), jnp.int32), steps=2)
    assert eng.moe_counters() is None
