"""The routing kernel's control and faults, each put in the kernel's
place through ``bench.systems.route.Recorder(replace=...)``.

* ``control(conf)``: the float64 reference recomputed with every
  operation rounded to bfloat16, one precision below the float32 that
  the configuration states.
* ``altered_answer()``: the kernel, with the offload flag of the middle
  request of every window flipped where it is produced.
* ``half_batch()``: the kernel, deciding only the first half of each
  window and handing the rest the first row's answer.
"""
from __future__ import annotations

import numpy as np

from bench.reference.routing import Reference


def _model_of(home_col: int, ref: Reference) -> str:
    return next(m for m, h in ref.home.items() if h == home_col)


def control(conf: dict):
    ref = Reference(conf, dtype="bfloat16")

    def kernel(lam, alpha, beta, gamma, mu, n, rtt, tau, home, up, table,
               impl=None, block_r=256):
        import jax.numpy as jnp
        lam = np.asarray(lam, np.float64)
        model = _model_of(int(np.asarray(home)[0]), ref)
        chosen, g, off, *_ = ref.guard(lam, model)
        return (jnp.asarray(chosen.astype(np.int32)),
                jnp.asarray(g.astype(np.float32)), jnp.asarray(off))
    return kernel


def altered_answer():
    from repro.kernels import ops
    orig = ops.routing_guard

    def kernel(*args, **kw):
        idx, g, off = (np.asarray(o) for o in orig(*args, **kw))
        home, up = np.asarray(args[8]), np.asarray(args[9])
        real = int(np.sum(up >= 0))     # padded rows carry up = -1
        r = max(real // 2, 0)
        off = off.copy()
        off[r] = not off[r]
        idx = idx.copy()
        idx[r] = up[r] if off[r] else home[r]
        return idx, g, off
    return kernel


def half_batch():
    from repro.kernels import ops
    orig = ops.routing_guard

    def kernel(*args, **kw):
        idx, g, off = (np.asarray(o).copy() for o in orig(*args, **kw))
        real = int(np.sum(np.asarray(args[9]) >= 0))
        h = max(real // 2, 1)
        idx[h:], g[h:], off[h:] = idx[0], g[0], off[0]
        return idx, g, off
    return kernel
