"""Profiler spans of the program.

Each span is a ``jax.profiler.TraceAnnotation`` named ``laimr.<name>``.
With no profiler session it costs about half a microsecond and records
nothing, so it needs no switch. Under a session, host spans and the
chip's operations land in one ``.xplane.pb`` on one clock, so an idle
gap of the device can be put down to the phase the host was in::

    with jax.profiler.trace("/tmp/laimr-profile"):
        plane.flush(t_now)          # or engine.step(), engine.generate()

``jax.profiler.ProfileData.from_file`` (or TensorBoard, or Perfetto with
``create_perfetto_trace=True``) reads the result; the keyword arguments
of a span arrive as the event's ``stats``.

The spans, and what each covers:

========================  ==================================================
span                      covers
========================  ==================================================
``laimr.plane.flush``     ``ControlPlane.flush`` after ``drain`` returned
                          requests: the policy's decision and the binding.
                          Args ``flush`` (this flush's number, 1 for the
                          plane's first) and ``rows`` (requests drained).
``laimr.policy.rates``    a policy's ``decide``: the rate matrix, the SLO
                          and lane-mask rows, for guard policies the home,
                          upstream and tau columns.
``laimr.policy.upload``   a fused path: row padding and every host-to-device
                          conversion of the flush, the device-resident
                          candidate columns and the Erlang-C table.
``laimr.kernel.launch``   the ``ops.routing_*`` call until it returns (an
                          asynchronous dispatch: the device may still run).
``laimr.policy.readback`` the host copies of the kernel's outputs (waits
                          for the device) and their slicing to the window.
``laimr.plane.bind``      the plane's per-request binding loop, redundant
                          copies included.
``laimr.engine.step``     ``ServingEngine.step``.
``laimr.engine.dispatch`` in ``step``: the jitted decode call, the argmax of
                          the logits and the position increment.
``laimr.engine.readback`` in ``step``: the host copy of the new tokens
                          (waits for the decode to finish).
``laimr.engine.merge``    in ``generate``: placing the prefill cache into the
                          engine's slots.
========================  ==================================================

Counters, kept on the device and read by the host once, when asked:

==========================  ================================================
counter                     counts
==========================  ================================================
``ServingEngine.moe_        int32 (MoE layers, 2), models with experts
counters()``                only: per MoE layer, in layer order, the experts
                            that received at least one row and the rows
                            routed (``slots * top_k`` a step: the serving
                            MoE drops none), each summed over decode steps
                            since the engine was built or
                            ``reset_moe_counters()``. The decode step adds
                            them on the device (the counters ride through
                            the jitted step, donated); ``moe_counters()``
                            is the one read. A model without experts keeps
                            none, and its step is unchanged.
==========================  ================================================

The four children of a flush nest inside ``laimr.plane.flush``:
``rates``, ``upload``, ``launch`` and ``readback`` run inside the
policy's ``decide``, then ``bind``. Policies without a fused backend
(``backend="vmap"``) have ``rates`` and no ``upload``, ``launch`` or
``readback``.
"""
from __future__ import annotations

import jax

PREFIX = "laimr."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The profiler span ``laimr.<name>``, a context manager; ``args``
    are recorded as the event's stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
