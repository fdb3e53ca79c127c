"""Where JAX keeps its persistent compilation cache, and what compiling
costs.

A restarted or resumed run that finds its compiled step in the cache skips
the compile. The cache only hits when the directory stays put, so the path
is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself, and nothing is set here), otherwise
``.jax_cache`` at the root of the checkout.

The cache key holds each op's metadata (its scope path and source line), so
an executable compiled from other source, say without the train step's
``jax.named_scope``s, is never taken for this one and the profiler names
each op's scope as this source sets it. Locations keep only the innermost
frame of the program's own code (and the whole scope path), so that the
key does not change with the call site of a jitted function.

``setup_compile_cache`` also makes JAX's compile events counters of
:mod:`repro.obs`: ``compile.seconds`` (tracing to a jaxpr, lowering to MLIR
and the backend compile, which takes in the cache lookup),
``compile.cache_hits`` and ``compile.cache_misses``.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

from repro import obs

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# JAX 0.9's names. The backend compile's span holds the persistent cache's
# read (``compile_or_get_cached``), so ``cache_retrieval_time_sec`` would
# count that time twice and is left out.
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile.cache_hits",
                "/jax/compilation_cache/cache_misses": "compile.cache_misses"}


class CompileClock:
    """Counts compile seconds, each nested event once.

    Tracing a jitted function traces every jitted function it calls, and
    lowering traces more, each reporting its own span. JAX reports a span's
    start as a scalar and its end as a time span, so a thread's depth says
    which span is outermost, and only those are counted."""

    def __init__(self):
        self._local = threading.local()

    def enter(self, event: str, _value: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self._local.depth = getattr(self._local, "depth", 0) + 1

    def __call__(self, event: str, start: float, end: float, **_) -> None:
        if event not in COMPILE_EVENTS:
            return
        self._local.depth = max(getattr(self._local, "depth", 0) - 1, 0)
        if self._local.depth == 0:
            obs.count("compile.seconds", end - start)


def _count_cache_event(event: str, **_) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        obs.count(name, 1)


_listening = False


def setup_compile_cache() -> str:
    """Place the persistent compilation cache and count compiles; call
    before the first compile. Returns the directory in use."""
    global _listening
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    if not _listening:
        clock = CompileClock()
        jax.monitoring.register_scalar_listener(clock.enter)
        jax.monitoring.register_event_time_span_listener(clock)
        jax.monitoring.register_event_listener(_count_cache_event)
        _listening = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
