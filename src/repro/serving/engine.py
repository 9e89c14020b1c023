"""Batched serving engine.

Provides the two pure functions the dry-run lowers for inference shapes
(``prefill_step`` / ``decode_step``) plus a small continuous-batching
engine used by the serving examples and the LA-IMR integration: requests
join/leave decode slots between steps, which is how the router's replica
pools map onto actual TPU batch slots.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import model
from repro.tracing import span

PyTree = Any


def make_prefill_fn(cfg: ArchConfig):
    """(params, batch) -> (last-token logits, cache). Lowered for
    prefill_* shapes."""
    def fn(params, batch):
        return model.prefill(params, cfg, batch)
    return fn


def make_decode_fn(cfg: ArchConfig):
    """(params, tokens, cache, pos) -> (logits, cache). ONE new token per
    sequence against a seq_len-deep cache — the decode_* dry-run shape."""
    def fn(params, tokens, cache, pos):
        return model.decode_step(params, cfg, tokens, cache, pos)
    return fn


def jit_decode(cfg: ArchConfig):
    """``make_decode_fn`` jitted as ``ServingEngine.step`` runs it, with
    the cache (argument 2) donated: the step writes each new K/V row into
    the buffers it was given, returns them as the new cache and copies
    nothing cache-sized. The caller drops its reference to the old cache
    (``step`` replaces it).

    A model with experts takes and returns its MoE counters too
    (``model.init_moe_counts``, argument 4, donated):
    (params, tokens, cache, pos, counts) -> (logits, cache, counts)."""
    if cfg.n_experts == 0:
        return jax.jit(make_decode_fn(cfg), donate_argnums=(2,))

    def fn(params, tokens, cache, pos, counts):
        return model.decode_step(params, cfg, tokens, cache, pos,
                                 moe_counts=counts)
    return jax.jit(fn, donate_argnums=(2, 4))


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, steps)
    steps: int


class ServingEngine:
    """Greedy batched generation with slot-based continuous batching.

    The engine owns a fixed-size decode batch (``slots``); sequences are
    assigned to free slots after prefill and release them on completion.
    This is the data-plane object an LA-IMR 'replica' models: its service
    rate is one decode step across all active slots.

    A model with experts serves them dropless and keeps, on the device,
    per MoE layer the experts each decode step touched and the rows it
    routed, summed over steps (``moe_counters``).
    """

    def __init__(self, cfg: ArchConfig, params: PyTree, slots: int,
                 max_len: int):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache = model.init_cache(cfg, slots, max_len)
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.active = np.zeros((slots,), bool)
        self.current = jnp.zeros((slots,), jnp.int32)
        self.moe_counts = model.init_moe_counts(cfg)
        self._decode = jit_decode(cfg)
        self._merge = jax.jit(_merge_cache, donate_argnums=(0,))
        self._prefill = jax.jit(
            lambda p, bb: model.prefill(p, self.cfg, bb))

    def free_slots(self) -> list[int]:
        return [i for i in range(self.slots) if not self.active[i]]

    def n_free(self) -> int:
        return int((~self.active).sum())

    def admit(self, slot: int, first_token: int, start_pos: int) -> None:
        self.active[slot] = True
        self.current = self.current.at[slot].set(first_token)
        self.pos = self.pos.at[slot].set(start_pos)

    def admit_next(self, first_token: int = 0,
                   start_pos: int = 0) -> Optional[int]:
        """Occupy the first free slot (batch-router admission surface);
        None when the decode batch is full."""
        for i in range(self.slots):
            if not self.active[i]:
                self.admit(i, first_token, start_pos)
                return i
        return None

    def release(self, slot: int) -> None:
        """Free a decode slot. Double release is a loud error: with
        redundant dispatch (first-completion cancellation) a silent
        second release would leave the continuous-batching slot count
        permanently off by one."""
        if not 0 <= slot < self.slots:
            raise IndexError(f"ServingEngine.release({slot}): no such "
                             f"slot (0..{self.slots - 1})")
        if not self.active[slot]:
            raise RuntimeError(
                f"ServingEngine.release({slot}): slot already free — "
                "double release (e.g. of a cancelled duplicate)")
        self.active[slot] = False

    def step(self) -> np.ndarray:
        """One decode step for all slots; returns the new tokens (B,)."""
        with span("engine.step"):
            with span("engine.dispatch"):
                if self.moe_counts is None:
                    logits, self.cache = self._decode(
                        self.params, self.current, self.cache, self.pos)
                else:
                    logits, self.cache, self.moe_counts = self._decode(
                        self.params, self.current, self.cache, self.pos,
                        self.moe_counts)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                self.current = nxt
                self.pos = self.pos + 1
            with span("engine.readback"):
                return np.asarray(nxt)

    def moe_counters(self) -> Optional[np.ndarray]:
        """int64 (MoE layers, 2), one read from the device: per MoE layer
        in layer order, the experts that received at least one row and
        the rows routed (slots * top_k a step, every slot decoding),
        each summed over the decode steps since the engine was built or
        ``reset_moe_counters``. None for a model without experts."""
        if self.moe_counts is None:
            return None
        return np.asarray(self.moe_counts).astype(np.int64)

    def reset_moe_counters(self) -> None:
        if self.moe_counts is not None:
            self.moe_counts = jnp.zeros_like(self.moe_counts)

    def generate(self, prompts: jax.Array, steps: int) -> GenerationResult:
        """Prefill ``prompts`` (B<=slots, S) then greedy-decode ``steps``."""
        b, s = prompts.shape
        assert b <= self.slots
        batch = {"tokens": prompts} if self.cfg.frontend == "tokens" else \
            {"embeddings": prompts}
        logits, cache = self._prefill(self.params, batch)
        # move the prefilled cache into the engine slots (b == slots fast
        # path adopts it whole)
        with span("engine.merge"):
            if b == self.slots:
                self.cache = cache
            else:
                self.cache = self._merge(self.cache, cache)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        self.current = jnp.zeros((self.slots,), jnp.int32).at[:b].set(first)
        self.pos = jnp.zeros((self.slots,), jnp.int32).at[:b].set(s)
        self.active[:b] = True
        out = [np.asarray(self.current[:b])]
        for _ in range(steps - 1):
            out.append(self.step()[:b])
        return GenerationResult(tokens=np.stack(out, axis=1), steps=steps)


def _merge_cache(full: PyTree, new: PyTree) -> PyTree:
    """Each leaf of the prefilled cache ``new`` written into the engine's
    cache ``full`` (donated, so in place) at the leading corner."""
    return jax.tree.map(_merge_batch, full, new)


def _merge_batch(full: jax.Array, new: jax.Array) -> jax.Array:
    """Write `new` into `full` at the leading corner.

    A prefilled cache leaf can be smaller than the engine's along BOTH
    the batch-slot axis (b < slots) and the cache-depth axis (prompt
    length < max_len), so every differing axis is sliced to ``new``'s
    extent — not just the first mismatch."""
    if full.shape == new.shape:
        return new
    idx = tuple(slice(0, ns) if fs != ns else slice(None)
                for fs, ns in zip(full.shape, new.shape))
    return full.at[idx].set(new)
