"""Program spans: named phases of the control round on the profiler's clock.

A :class:`Span` is a context manager around one phase of the program.
It opens a ``jax.profiler.TraceAnnotation`` of its name, so under a
profiler session the phase sits on the trace's host plane, on the same
clock as the device planes and nested inside whatever span was open
around it, and it times the phase with ``time.perf_counter``
(``seconds``, which the engine's round profile and the fused segments
keep).  With no profiler session the cost is that clock pair and the
annotation's enabled check (about a microsecond).

The trace is the spans' only record: a span's parent is the span open
around it, and its self time is its time less the spans open inside
it, both read off the host plane.  The engine opens ``engine.round``
around a whole round, with the round index as an annotation argument,
so every program span of a round nests under it.

Names are dotted by layer: ``engine.*`` (``cluster/sim.py``),
``controller.*`` (``cluster/controller.py``) and ``fused.*``
(``core/mckp.py`` ``_fused_run``).
"""

from __future__ import annotations

import time

_annotation = None


def _trace_annotation():
    # jax is imported at the first span, not with this module: the
    # program's host-only modules import this one, and a caller may set
    # JAX's environment after importing them
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class Span:
    """``with Span("fused.wait") as sp:`` — one named phase.

    ``args`` become arguments of the trace annotation (``engine.round``
    carries its round index).  After the block, ``seconds`` is the
    phase's duration."""

    __slots__ = ("name", "args", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._ann = (_annotation or _trace_annotation())(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
