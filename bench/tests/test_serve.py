"""The served cell at a tiny size (the widths cut, the code paths as
timed): a sound run is correct; a token altered where the engine
produces it, and the int8 control decoding in the engine's place, each
make ``correct`` false against the cell's own limit; and the int8
control reads a wider gap than the program on the same served tokens."""
import functools
import time
import types

import jax
import numpy as np
import pytest

from bench import harness, traffic, weights
from bench.systems import serve
from bench.reference import stablelm

SEED = 2**31 + 5


@pytest.fixture
def cell():
    cell = harness.find_cell("stablelm3b_edge.decode_heavy")
    # the smallest widths at which the int8 control's mean gap passes
    # the cell's limit on this seed; it reads 6.4e-3 to 7.4e-3 at the
    # cell's own size (PERF.md)
    cell.config = dict(cell.config, hidden_size=256, intermediate_size=512,
                       num_hidden_layers=4, num_attention_heads=4,
                       num_key_value_heads=4, vocab_size=8192,
                       engine={"slots": 4, "max_len": 64})
    cell.traffic = dict(cell.traffic, prompt_len=16, new_tokens=16)
    cell.params = dict(cell.params, wave_requests=3, sample_requests=6)
    return cell


def _run(cell, engine_class=None):
    line, _ = harness.execute(
        cell, SEED, 0.5, False, time.perf_counter(), jax.devices(),
        system=lambda run: serve.run(run, engine_class=engine_class))
    return line


class Int8Engine:
    """The control in the engine's place: the reference at int8
    (``stablelm.int8_next``) decoding greedily, the whole sequence
    again at every step."""

    def __init__(self, conf, arch, params, slots, max_len):
        self.conf, self.params, self.slots = conf, params, slots

    def _next(self):
        tok = np.asarray(stablelm.int8_next(self.params, self.conf,
                                            self.seq))
        self.seq = np.concatenate([self.seq, tok[:, None]], axis=1)
        return tok

    def generate(self, prompts, steps=1):
        self.seq = np.asarray(prompts)
        return types.SimpleNamespace(tokens=self._next()[:, None])

    def step(self):
        tok = self._next()
        return np.pad(tok, (0, self.slots - tok.size))


def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"served_tokens_per_s", "setup_s"}


def test_altered_token_makes_run_incorrect(cell, monkeypatch):
    from repro.serving.engine import ServingEngine
    orig = ServingEngine.step

    def step(self):
        # every slot's token of every step, one id off the greedy one
        out = (orig(self) + 1) % self.cfg.vocab_size
        self.current = jax.numpy.asarray(out)
        return out
    monkeypatch.setattr(ServingEngine, "step", step)
    line = _run(cell)
    assert not line["correct"], line["checks"]


def test_int8_control_in_engine_place_is_incorrect(cell):
    line = _run(cell, functools.partial(Int8Engine, cell.config))
    assert not line["correct"], line["checks"]
    limit = harness.find_cell(cell.name).params["limits"]["served_gap_mean"]
    assert line["checks"]["served_gap_mean"]["limit"] == limit


def test_int8_control_reads_wider_than_program(cell):
    conf = cell.config
    params = weights.make(conf, traffic.jax_seed(SEED))
    prompts = traffic.process(cell.traffic).prompts(cell.traffic, SEED, 0, 4,
                                                    conf["vocab_size"])
    # greedy tokens of the float32 reference itself: gap 0 by definition
    seq = np.asarray(prompts)
    for _ in range(16):
        g = stablelm._forward(params, jax.numpy.asarray(seq),
                              dict(stablelm._frozen(conf)),
                              stablelm._mm_f32)[:, -1]
        logits = stablelm._mm_f32(g, params["lm_head"], "bd,dv->bv")
        seq = np.concatenate([seq, np.asarray(logits.argmax(-1))[:, None]],
                             axis=1)
    served = seq[:, 16:]
    prompts = seq[:, :16]
    own = np.asarray(stablelm.served_gaps(params, conf, prompts, served))
    ctl = np.asarray(stablelm.int8_gaps(params, conf, prompts, served))
    assert own.max() < 1e-4
    assert ctl.max() > 10 * max(own.max(), 1e-6)
