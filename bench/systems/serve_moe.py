"""Served sparse-MoE cells: closed waves through one ``ServingEngine``
replica of a decoder whose every MLP is a top-k mixture of experts and
whose layers mix sliding-window and full attention (Mellum2).

It runs as ``bench/systems/serve.py`` does (set-up draws the weights on
the device from the seed, builds ``ServingEngine(slots, max_len)`` and
serves one whole wave; the window serves waves of ``wave_requests``
prompts, each prefilled through ``generate(steps=1)`` and decoded
``new_tokens`` more steps through ``step``), with the weights of
``bench/weights_moe.py`` and the reference ``bench/reference/mellum2.py``.

Besides ``served_gap_mean`` it checks that no row was dropped: the
engine's MoE counters, zeroed after set-up and read once after the
window, must show ``slots * top_k`` rows routed in every MoE layer at
every decode step of the window (``dropped_rows``, limit 0). The
counters' experts touched give the bytes ``moe_gmm`` needs in decode.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import flops_moe, traffic, weights_moe
from bench.systems.serve import compare, serve_wave

KERNEL = "decode_attention"
GMM = "moe_gmm"
_KIND = {"sliding_attention": "local", "full_attention": "attn"}


def arch_config(conf: dict):
    """The program's configuration object for a config file. Raises on
    a setting the program does not serve."""
    from repro.configs.base import ArchConfig, Yarn
    kinds = weights_moe.pattern(conf)
    rope = conf["rope_parameters"]
    full, local = rope["full_attention"], rope["sliding_attention"]
    if (local["rope_type"] != "default"
            or local["rope_theta"] != full["rope_theta"]
            or conf["rms_norm_eps"] != 1e-6 or conf["hidden_act"] != "silu"
            or not conf["norm_topk_prob"] or conf["attention_bias"]):
        raise ValueError("a setting the program does not serve")
    yarn = None
    if full["rope_type"] == "yarn":
        yarn = Yarn(factor=float(full["factor"]),
                    original_max_positions=int(
                        full["original_max_position_embeddings"]),
                    beta_fast=float(full["beta_fast"]),
                    beta_slow=float(full["beta_slow"]),
                    attention_factor=float(full["attention_factor"]))
    return ArchConfig(
        name=conf["model_type"], arch_type="moe", source=conf["source"],
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["moe_intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"],
        layer_pattern=tuple(_KIND[k] for k in kinds),
        window=conf["sliding_window"], n_experts=conf["num_experts"],
        top_k=conf["num_experts_per_tok"], mlp_kind="swiglu",
        norm="rmsnorm", rope_theta=float(full["rope_theta"]),
        global_yarn=yarn, tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"])


def needs(conf: dict, n: int, plen: int, new: int, waves: int,
          counts: np.ndarray) -> dict:
    """What the window needed, from shapes and the routing counters:
    the model's FLOPs (active experts only), and per kernel the (FLOPs,
    bytes, launches) of its calls."""
    kinds = flops_moe.layer_kinds(conf)
    need = n * waves * (flops_moe.prefill_flops(conf, plen) + sum(
        flops_moe.decode_flops(conf, plen + s) for s in range(new)))
    attention = []
    for kind in set(kinds):
        calls = waves * kinds.count(kind)
        for s in range(new):
            f, b = flops_moe.decode_attention_need(conf, kind, plen + s)
            attention.append((n * f, n * b, calls))
    steps = waves * new
    gmm = [(f, b, waves * len(kinds))
           for f, b in flops_moe.gmm_prefill_need(conf, n * plen)]
    for touched, rows in counts:
        gmm += [(f, b, steps) for f, b in flops_moe.gmm_decode_need(
            conf, rows / steps, touched / steps)]
    return {"model_flops": need, "decode_attention": attention, GMM: gmm}


def run(run, engine_class=None):
    """One run of a served MoE cell (``bench.harness.Run``)."""
    import jax
    from bench.harness import Outcome, Spans
    from bench.reference import mellum2
    cell = run.cell
    conf, mix = cell.config, cell.traffic
    arch = arch_config(conf)
    if engine_class is None:
        from repro.serving.engine import ServingEngine as engine_class
    n = int(cell.params["wave_requests"])
    new = int(mix["new_tokens"])
    plen = int(mix["prompt_len"])
    vocab = conf["vocab_size"]
    eng = conf["engine"]
    if n >= eng["slots"]:
        raise ValueError("a wave must leave one of the engine's slots free")
    params = weights_moe.make(conf, traffic.jax_seed(run.seed))
    jax.block_until_ready(params)
    engine = engine_class(arch, params, slots=eng["slots"],
                          max_len=eng["max_len"])
    waves_of = traffic.process(mix)
    warm = waves_of.prompts(mix, run.seed, -1, n, vocab)
    serve_wave(engine, warm, new, Spans(), n)
    engine.reset_moe_counters()
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
    counts = engine.moe_counters()
    tokens = sum(t.size for _, t in waves)
    window_s = run.window_s
    del engine, params
    gc.collect()

    # every MoE layer routed top_k rows of every slot at every step
    routed = len(waves) * new * eng["slots"] * conf["num_experts_per_tok"]
    dropped = int(np.abs(routed - counts[:, 1]).sum())

    # the reference, over a seed-drawn sample of the finished requests
    reqs = [(p[j], t[j]) for p, t in waves for j in range(n)]
    pick = traffic.rng_for(run.seed, 2).permutation(len(reqs))
    pick = np.sort(pick[:int(cell.params["sample_requests"])])
    prompts = np.stack([reqs[j][0] for j in pick])
    served = np.stack([reqs[j][1] for j in pick])
    params = weights_moe.make(conf, traffic.jax_seed(run.seed))
    gaps = np.asarray(mellum2.served_gaps(params, conf, prompts, served))
    del params
    gap_mean = float(gaps.mean())
    touched = counts[:, 0].sum() / max(1, len(waves) * new * len(counts))
    print(f"serve_moe: {len(waves)} waves, {len(reqs)} requests, {tokens} "
          f"tokens in {window_s:.3f}s; rows routed {int(counts[:, 1].sum())}"
          f" of {routed * len(counts)}, experts touched a layer-step "
          f"{touched:.3f}; reference over {gaps.size} served tokens, gap "
          f"mean {gap_mean:.6f}, widest {float(gaps.max()):.6f}, share off "
          f"the reference's first {float((gaps > 0).mean()):.4f}")

    checks = dict(compare(gaps, cell.params["limits"]),
                  dropped_rows=(dropped, cell.params["limits"]["dropped_rows"]))
    extra = {"kernel": KERNEL, "gmm_kernel": GMM,
             "sample": (prompts, served, gaps),
             **needs(conf, n, plen, new, len(waves), counts)}
    return Outcome(attempted=len(reqs), failed=0,
                   metrics={"served_tokens_per_s": tokens / window_s},
                   checks=checks, extra=extra)
