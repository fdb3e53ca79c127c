"""Spans and counters at the program's layer boundaries.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: inert unless a
profiler trace is running, and then a host event on the profiler's clock,
which the device planes share. Every program span is named ``transom.*``.

``count(name, n)`` opens a zero-length ``transom.count`` span carrying
``counter`` and ``n``, so that a reader of a trace sums the counts that fall
inside any window of it. Nothing keeps a count outside the trace.

Nothing is written anywhere, and there is no switch: tracing is off when no
profiler runs. A process that never imported JAX runs no profiler, so there
the spans are empty contexts and JAX is not imported for them.
"""
from __future__ import annotations

import contextlib
import sys


def span(name: str, **meta):
    """A host span ``name`` with ``meta`` as its stats."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **meta)


def step_span(step_num: int):
    """The ``transom.step`` span of one training step."""
    import jax
    return jax.profiler.StepTraceAnnotation("transom.step", step_num=step_num)


def count(name: str, n: float) -> None:
    """Count ``n`` of ``name`` in the trace."""
    with span("transom.count", counter=name, n=n):
        pass
