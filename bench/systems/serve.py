"""Served cells: closed waves through one ``ServingEngine`` replica.

Set-up draws the weights on the device from the seed (``bench/weights``),
builds ``ServingEngine(slots, max_len)`` and serves one whole wave, so
that the prefill, the merge into the engine's cache and the decode step
are compiled (or loaded from the cache) before the window. In the window
each wave prefills ``wave_requests`` prompts through
``generate(steps=1)``, which also serves each request's first token, and
then decodes ``new_tokens`` more through ``step``. Waves follow each
other until the window has lasted ``--seconds``; the window ends with
the wave that crosses it, so every request in it finishes.

A wave holds ``slots - 1`` requests: a wave that fills every slot makes
``generate`` adopt the prefill cache whole, only as deep as the prompt,
and the ring buffer then overwrites the prompt's first positions as
soon as decoding passes its end.

After the window, with the engine freed, a seed-drawn sample of the
finished requests goes through the float32 reference
(``bench/reference/stablelm.py``): ``served_gap_mean`` is the mean gap
by which a served token's logit lies below the reference's best (the
widest such gap is printed too; it swings from seed to seed with the
one token nearest a tie, see PERF.md).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import flops, traffic, weights

KERNEL = "decode_attention"


def arch_config(conf: dict):
    """The program's configuration object for a config file."""
    from repro.configs.base import ArchConfig
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    if conf["assumed"]["partial_rotary_factor"] != 1.0:
        raise ValueError("the program rotates whole heads: "
                         "partial_rotary_factor must be 1.0")
    return ArchConfig(
        name=conf["model_type"], arch_type="dense", source=conf["source"],
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=d // h, layer_pattern=("attn",), mlp_kind="swiglu",
        norm="layernorm", rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"])


def serve_wave(engine, prompts, new_tokens: int, spans, n: int):
    """One wave: prefill + first token, then ``new_tokens`` decode steps.
    Returns (n, new_tokens + 1) served tokens."""
    import jax.numpy as jnp
    with spans.span("prefill"):
        first = engine.generate(jnp.asarray(prompts), steps=1).tokens[:, 0]
    out = [first]
    for _ in range(new_tokens):
        with spans.span("decode_step"):
            out.append(engine.step()[:n])
    return np.stack(out, axis=1)


def compare(gaps: np.ndarray, limits: dict) -> dict:
    """The numbers compared, each with its limit."""
    return {"served_gap_mean": (float(np.mean(gaps)),
                                limits["served_gap_mean"])}


def run(run, engine_class=None):
    """One run of a served cell (``bench.harness.Run``). ``engine_class``
    puts another engine in ``ServingEngine``'s place (the control's
    test uses it)."""
    import jax
    from bench.harness import Outcome, Spans
    from bench.reference import stablelm
    if engine_class is None:
        from repro.serving.engine import ServingEngine as engine_class
    cell = run.cell
    conf, mix = cell.config, cell.traffic
    n = int(cell.params["wave_requests"])
    new = int(mix["new_tokens"])
    plen = int(mix["prompt_len"])
    vocab = conf["vocab_size"]
    eng = conf["engine"]
    if n >= eng["slots"]:
        raise ValueError("a wave must leave one of the engine's slots free")
    params = weights.make(conf, traffic.jax_seed(run.seed))
    jax.block_until_ready(params)
    engine = engine_class(arch_config(conf), params, slots=eng["slots"],
                          max_len=eng["max_len"])
    waves_of = traffic.process(mix)
    warm = waves_of.prompts(mix, run.seed, -1, n, vocab)
    serve_wave(engine, warm, new, Spans(), n)
    gc.collect()
    gc.freeze()
    run.begin_window()
    waves = []
    while True:
        p = waves_of.prompts(mix, run.seed, len(waves), n, vocab)
        waves.append((p, serve_wave(engine, p, new, run.spans, n)))
        if time.perf_counter() - run.window_t0 >= run.seconds:
            break
    run.end_window()
    gc.unfreeze()
    tokens = sum(t.size for _, t in waves)
    window_s = run.window_s
    del engine, params
    gc.collect()

    # the reference, over a seed-drawn sample of the finished requests
    reqs = [(p[j], t[j]) for p, t in waves for j in range(n)]
    pick = traffic.rng_for(run.seed, 2).permutation(len(reqs))
    pick = np.sort(pick[:int(cell.params["sample_requests"])])
    prompts = np.stack([reqs[j][0] for j in pick])
    served = np.stack([reqs[j][1] for j in pick])
    params = weights.make(conf, traffic.jax_seed(run.seed))
    gaps = np.asarray(stablelm.served_gaps(params, conf, prompts, served))
    del params
    gap_mean = float(gaps.mean())
    print(f"serve: {len(waves)} waves, {len(reqs)} requests, {tokens} tokens "
          f"in {window_s:.3f}s; reference over {gaps.size} served tokens, "
          f"gap mean {gap_mean:.6f}, widest {float(gaps.max()):.6f}, "
          f"share off the reference's first {float((gaps > 0).mean()):.4f}")

    # the work the window needed, from shapes
    need = len(reqs) * (flops.prefill_flops(conf, plen) + sum(
        flops.decode_flops(conf, plen + s) for s in range(new)))
    calls = len(waves) * conf["num_hidden_layers"]
    extra = {"model_flops": need, "kernel": KERNEL,
             "sample": (prompts, served, gaps),
             # (FLOPs, bytes, launches) of the decode-attention calls of
             # each step of a wave: one launch per layer
             "decode_attention": [
                 (n * f, n * b, calls) for f, b in (
                     flops.decode_attention_need(conf, plen + s)
                     for s in range(new))]}
    return Outcome(attempted=len(reqs), failed=0,
                   metrics={"served_tokens_per_s": tokens / window_s},
                   checks=compare(gaps, cell.params["limits"]),
                   extra=extra)
