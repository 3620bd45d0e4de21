"""Spans at the serving engine's layer boundaries, on the profiler's clock.

    with span("pipeline.dispatch", node=name, batch=b):
        out = fn(x)

A span is a ``jax.profiler.TraceAnnotation``, written into the profiler's
own trace beside the runtime's events and the device's operations, so
all of them share one clock; its keyword arguments become the event's
stats.  It is recorded only while a profiler session runs in this
process (``jax.profiler.trace``, ``start_trace``, or a capture through
``start_server``): otherwise ``span`` returns a shared no-op context and
costs one ``is_enabled()`` check.  There is no switch of its own, and no
store besides the trace.

Spans mark per-step, per-micro-batch and per-node work, never single
requests:

``engine.step``        ``ServingEngine.step``: drain, run, completion
``batcher.form``       one micro-batch stacked and padded
``pipeline.h2d``       an input staged on the device
``pipeline.dispatch``  one plan node's call (``node``: its stable name;
                       a node with residual blocks adds ``res_blocks``
                       and ``stream_bytes``, the int32 stream they read
                       and write at the node's batch)
``pipeline.d2h``       ``np.asarray`` of a node's device result
``engine.complete``    one micro-batch's requests completed
``python.gc``          a garbage-collector pause (``generation``)
"""

from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation


class _Off:
    """What ``span`` returns while no profiler session runs."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


_OFF = _Off()


def span(name: str, **args):
    """A context that records `name`, with `args` as its stats, in the
    running profiler session; a no-op without one.  Inside it,
    ``set_metadata(**more)`` adds stats known only later."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(name, **args)


class _GcSpans:
    """The ``gc.callbacks`` hook: a ``python.gc`` span from each
    collection's start to its stop, while a session runs."""

    def __init__(self):
        self.open = None

    def __call__(self, phase, info):
        if phase == "start":
            if TraceAnnotation.is_enabled():
                self.open = TraceAnnotation(
                    "python.gc", generation=info["generation"]
                )
                self.open.__enter__()
        elif self.open is not None:
            ann, self.open = self.open, None
            ann.__exit__(None, None, None)


gc.callbacks.append(_GcSpans())
