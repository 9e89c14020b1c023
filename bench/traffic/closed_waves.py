"""Closed waves of requests: prompts of ``prompt_len`` random token ids,
each answered with ``new_tokens`` greedy tokens. The cell says how many
requests a wave holds."""
import numpy as np

from bench import traffic


def prompts(mix: dict, seed: int, wave: int, n: int,
            vocab: int) -> np.ndarray:
    """(n, prompt_len) int32 token ids of wave ``wave``; wave -1 is the
    one set-up serves."""
    rng = (traffic.rng_for(seed, 1, wave) if wave >= 0
           else traffic.rng_for(seed, 3))
    return rng.integers(0, vocab, size=(n, int(mix["prompt_len"])),
                        dtype=np.int32)
