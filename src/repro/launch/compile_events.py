"""A process-wide log of the compile events JAX reports.

JAX times the tracing, lowering and compiling of every program it builds
and reports each as a ``jax.monitoring`` duration event; nothing keeps
them unless a listener does.  ``import repro`` calls :func:`install`,
which registers one listener that appends each event of :data:`EVENTS`
with the wall-clock time (``time.time_ns``) at which it ended, so a
caller can tell the compiles of a run's set-up from those that came
later:

    from repro.launch import compile_events
    compile_events.events()      # [(event, seconds, end_ns), ...]

A backend compile that the persistent cache serves still reports
``COMPILE``, with ``CACHE_READ`` inside it.  Traces nest (a jitted
function traces the jitted functions it calls), so summed ``TRACE``
seconds can exceed the wall time they took.
"""
from __future__ import annotations

import time

import jax

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
EVENTS = (TRACE, LOWER, COMPILE, CACHE_READ)

_log: list[tuple[str, float, int]] = []
_installed = False


def _listen(event: str, seconds: float, **_) -> None:
    if event in EVENTS:
        _log.append((event, float(seconds), time.time_ns()))


def install() -> None:
    """Register the listener (once per process)."""
    global _installed
    if not _installed:
        jax.monitoring.register_event_duration_secs_listener(_listen)
        _installed = True


def events() -> list[tuple[str, float, int]]:
    """Every event logged so far, oldest first."""
    return list(_log)
