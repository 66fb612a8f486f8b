"""The JAX runtime's own work, counted in the program.

One process-wide ``jax.monitoring`` listener (installed by
:func:`install` on the first multiply) counts what JAX reports:

  traces      ``/jax/core/compile/jaxpr_trace_duration`` and its seconds
  lowerings   ``/jax/core/compile/jaxpr_to_mlir_module_duration``, its
              seconds, and the ``fun_name`` of each lowered program
  compiles    ``/jax/core/compile/backend_compile_duration`` (compile
              requests, persistent-cache hits included) and its seconds
  cache_hits  ``/jax/compilation_cache/cache_hits``

A span that asks for them takes a :func:`mark` when it opens and reads
:func:`since` when it closes: the deltas over its interval, with the
distinct lowered names.  While telemetry is enabled the counts are also
published into the registry as ``jax.*``.

This module imports jax only inside :func:`install`.
"""
from __future__ import annotations

import threading
from typing import Dict, List

from . import metrics, telemetry

__all__ = ["install", "mark", "since", "COUNTS"]

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

NAMES_MAX = 200

_INTS = ("traces", "lowerings", "compiles", "cache_hits")
_SECONDS = ("trace_s", "lower_s", "compile_s")
COUNTS: Dict[str, float] = {k: 0 for k in _INTS + _SECONDS}

_installed = False
_open: List["_Mark"] = []   # open marks, which collect lowered names
# the listener runs on whichever thread compiles
_lock = threading.Lock()


class _Mark:
    __slots__ = ("counts", "lowered")

    def __init__(self):
        self.counts = dict(COUNTS)
        self.lowered: Dict[str, None] = {}


def _count(key: str, amount=1) -> None:
    with _lock:
        COUNTS[key] += amount
    if telemetry.enabled():
        metrics.counter(f"jax.{key}").inc(amount)


def _on_duration(event: str, duration: float, fun_name: str = "",
                 **_) -> None:
    if event == LOWER:
        _count("lowerings")
        _count("lower_s", duration)
        with _lock:
            for m in _open:
                m.lowered[fun_name] = None
    elif event == TRACE:
        _count("traces")
        _count("trace_s", duration)
    elif event == COMPILE:
        _count("compiles")
        _count("compile_s", duration)


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT:
        _count("cache_hits")


def install() -> None:
    """Register the listener once per process (listeners cannot be
    taken off again)."""
    global _installed
    if _installed:
        return
    import jax

    with _lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _installed = True


def mark() -> _Mark:
    """Open a counting interval."""
    with _lock:
        m = _Mark()
        _open.append(m)
    return m


def since(m: _Mark) -> Dict[str, object]:
    """Close ``m``: the counts since it opened, and ``lowered``, the
    distinct names of the programs lowered, joined by ``;`` and cut to
    ``NAMES_MAX`` characters."""
    with _lock:
        _open.remove(m)
        counts = dict(COUNTS)
    out: Dict[str, object] = {k: int(counts[k] - m.counts[k])
                              for k in _INTS}
    out.update({k: float(counts[k] - m.counts[k]) for k in _SECONDS})
    # the profiler's metadata encoding ends a value at ',' or '#'
    names = ";".join(n.replace(",", " ").replace("#", " ")
                     for n in m.lowered)
    out["lowered"] = names[:NAMES_MAX]
    return out
