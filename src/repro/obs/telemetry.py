"""Structured spans, on the profiler's clock, and the predicted-vs-actual
plan-outcome log.

A span is one API with two sinks.  ``span(name)`` always opens a
profiler annotation named ``dbcsr.<name>`` (``jax.profiler.
TraceAnnotation``): under a profiler session it lands on the caller's
host thread in the profiler's own trace, on the same clock as the
device ops; with no session it is an inactive TraceMe.  Under
``enable()`` the span is also kept as a :class:`SpanRecord` — a
host-side timed interval with parent/child nesting — for the multiply
pipeline:

    multiply                       (root, one per dbcsr.multiply)
      plan                         norms, masks, occupancy, planner
      stacks                       the local multiply: stack plans,
                                   per-step masks and norms
      dispatch                     the schedule engine's call: the
                                   densified path's cached program
                                   (``program_cache`` hit or miss) or
                                   the blocked path's eager shard_map
                                   (trace, lowering, compile-cache
                                   lookup); launch
      verify                       ABFT checksum verification
        repair                     re-execution after a detection
          dispatch
      finish                       executor/schedule stats, result mask

``span(..., counters=True)`` (the root and ``dispatch``) attaches the
JAX runtime's counts over the span (``runtime.py``: ``lowerings``,
``traces``, ``compiles``, ``cache_hits``, ``lower_s``, ``trace_s``,
``compile_s``, ``lowered``) to the annotation's metadata and the
record's attrs, computed only when a profiler session runs or
telemetry is on.

Call sites gate spans on ``not under jax.jit tracing`` (``maybe_span``
with the operands' tracer check); telemetry decides only the records,
the plan-outcome log and the enabled path's synchronisation.  Telemetry
is OFF by default and the contract is *bit identical results and zero
registry entries* when off.

``enable(log_dir=...)`` additionally appends every completed trace to
``<log_dir>/events.jsonl`` and every plan outcome (predicted vs
measured cost per executed plan) to ``<log_dir>/plan_outcomes.jsonl``
— the file ``planner.calibrate --check-drift`` consumes.

This module imports jax only when a span first opens, and nothing from
``repro.core`` / ``repro.planner`` (they import us).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
from typing import Dict, List, Optional

from . import runtime

__all__ = [
    "SpanRecord", "Tracer", "enable", "disable", "enabled",
    "span", "maybe_span", "last_trace",
    "record_plan_outcome", "plan_outcomes", "clear_plan_outcomes",
    "EVENTS_LOG", "PLAN_OUTCOMES_LOG", "PROFILE_PREFIX",
]

PROFILE_PREFIX = "dbcsr."
EVENTS_LOG = "events.jsonl"
PLAN_OUTCOMES_LOG = "plan_outcomes.jsonl"


@dataclasses.dataclass
class SpanRecord:
    """One timed interval.  ``t0`` is ``time.perf_counter()`` seconds;
    ``dur`` is seconds."""

    name: str
    cat: str
    span_id: int
    parent_id: Optional[int]
    trace_id: int
    t0: float
    dur: float = -1.0
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "name": self.name, "cat": self.cat, "span_id": self.span_id,
            "parent_id": self.parent_id, "trace_id": self.trace_id,
            "t0": self.t0, "dur": self.dur, "attrs": dict(self.attrs),
        }

    @staticmethod
    def from_dict(d: dict) -> "SpanRecord":
        return SpanRecord(
            name=d["name"], cat=d.get("cat", "span"),
            span_id=int(d["span_id"]), parent_id=d.get("parent_id"),
            trace_id=int(d.get("trace_id", d["span_id"])),
            t0=float(d["t0"]), dur=float(d["dur"]),
            attrs=dict(d.get("attrs") or {}))


_ANNOTATION = None   # jax.profiler.TraceAnnotation, imported on first use


class _Span:
    """An open span: the profiler annotation ``dbcsr.<name>``, the
    :class:`SpanRecord` under ``enable()``, and with ``counters`` the
    JAX runtime's counts over the span on both.  ``set()`` attaches
    attrs to the record and, under a profiler session, to the
    annotation's metadata."""

    __slots__ = ("_name", "_cat", "_attrs", "_counters", "_ann", "_mark",
                 "_tracer", "rec")

    def __init__(self, name: str, cat: str, counters: bool, attrs: dict):
        self._name = name
        self._cat = cat
        self._attrs = attrs
        self._counters = counters
        self._mark = None
        self._tracer = None
        self.rec: Optional[SpanRecord] = None

    def set(self, **attrs) -> None:
        if self.rec is not None:
            self.rec.attrs.update(attrs)
        if self._ann.is_enabled():
            self._ann.set_metadata(**attrs)

    def recording(self) -> bool:
        """Whether ``set()`` lands anywhere: a record under ``enable()``
        or the annotation of a running profiler session."""
        return self.rec is not None or self._ann.is_enabled()

    def __enter__(self) -> "_Span":
        global _ANNOTATION
        if _ANNOTATION is None:
            from jax.profiler import TraceAnnotation

            _ANNOTATION = TraceAnnotation
        self._ann = _ANNOTATION(PROFILE_PREFIX + self._name)
        self._ann.__enter__()
        if _ENABLED and _TRACER is not None:
            self._tracer = _TRACER
            self.rec = _TRACER.begin(self._name, self._cat, **self._attrs)
        if self._counters:
            runtime.install()
            if self.rec is not None or self._ann.is_enabled():
                self._mark = runtime.mark()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self._mark is not None:
                self.set(**runtime.since(self._mark))
            if self.rec is not None:
                if exc_type is not None:
                    self.rec.attrs.setdefault("error", exc_type.__name__)
                self._tracer.end(self.rec)
        finally:
            self._ann.__exit__(exc_type, exc, tb)
        return False


class _NoopSpan:
    """Shared do-nothing span for a call site under jit tracing."""

    __slots__ = ()
    rec = None

    def set(self, **attrs) -> None:
        pass

    def recording(self) -> bool:
        return False

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Collects spans; nesting follows an explicit begin/end stack."""

    def __init__(self, log_dir: Optional[str] = None):
        self.spans: List[SpanRecord] = []
        self.log_dir = log_dir
        self._stack: List[SpanRecord] = []
        self._ids = itertools.count(1)
        self._root_ids: List[int] = []

    # -- core span lifecycle -------------------------------------------
    def begin(self, name: str, cat: str = "span", **attrs) -> SpanRecord:
        parent = self._stack[-1] if self._stack else None
        sid = next(self._ids)
        rec = SpanRecord(
            name=name, cat=cat, span_id=sid,
            parent_id=parent.span_id if parent else None,
            trace_id=parent.trace_id if parent else sid,
            t0=time.perf_counter(), attrs=dict(attrs))
        self._stack.append(rec)
        return rec

    def end(self, rec: SpanRecord) -> None:
        rec.dur = time.perf_counter() - rec.t0
        # tolerate a stack skew from an exception mid-span: pop to rec
        while self._stack:
            top = self._stack.pop()
            if top is rec:
                break
        self.spans.append(rec)
        if rec.parent_id is None:
            self._root_ids.append(rec.span_id)
            self._flush_trace(rec)

    def current(self) -> Optional[SpanRecord]:
        return self._stack[-1] if self._stack else None

    # -- trace queries -------------------------------------------------
    def trace(self, trace_id: int) -> List[SpanRecord]:
        out = [s for s in self.spans if s.trace_id == trace_id]
        out.sort(key=lambda s: (s.t0, s.span_id))
        return out

    def last_trace(self) -> List[SpanRecord]:
        if not self._root_ids:
            return []
        return self.trace(self._root_ids[-1])

    def clear(self) -> None:
        self.spans.clear()
        self._stack.clear()
        self._root_ids.clear()

    # -- JSONL event log -----------------------------------------------
    def _flush_trace(self, root: SpanRecord) -> None:
        if not self.log_dir:
            return
        path = os.path.join(self.log_dir, EVENTS_LOG)
        with open(path, "a") as f:
            for s in self.trace(root.trace_id):
                f.write(json.dumps(s.to_dict()) + "\n")


# -- module state ------------------------------------------------------
_ENABLED = False
_TRACER: Optional[Tracer] = None
_LOG_DIR: Optional[str] = None
_PLAN_OUTCOMES: List[dict] = []


def enable(log_dir: Optional[str] = None, *, reset: bool = True) -> Tracer:
    """Turn telemetry on.  ``log_dir`` additionally streams completed
    traces and plan outcomes to JSONL files there.  ``reset=False``
    keeps an existing tracer's spans across enable/disable cycles."""
    global _ENABLED, _TRACER, _LOG_DIR
    if log_dir is not None:
        os.makedirs(log_dir, exist_ok=True)
    _LOG_DIR = log_dir
    if _TRACER is None or reset:
        _TRACER = Tracer(log_dir=log_dir)
    else:
        _TRACER.log_dir = log_dir
    _ENABLED = True
    return _TRACER


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def span(name: str, cat: str = "span", *, counters: bool = False,
         **attrs) -> _Span:
    """Open a span: the profiler annotation ``dbcsr.<name>`` always, a
    record on the active tracer when enabled; ``counters`` attaches the
    JAX runtime's counts over the span to both."""
    return _Span(name, cat, counters, attrs)


def maybe_span(cond: bool, name: str, cat: str = "span", **attrs):
    """``span()`` gated on a call-site flag: False (the operands are
    ``jax.jit`` tracers) gives the shared no-op span."""
    if not cond:
        return NOOP_SPAN
    return span(name, cat, **attrs)


def last_trace() -> List[SpanRecord]:
    return _TRACER.last_trace() if _TRACER is not None else []


# -- predicted-vs-actual planner accounting ----------------------------
def record_plan_outcome(**fields) -> None:
    """Log one executed plan: ``algorithm``, ``predicted_s``,
    ``measured_s`` plus free-form context (geometry, densify,
    occupancy).  Feeds the planner scoreboard and
    ``planner.calibrate --check-drift``."""
    if not _ENABLED:
        return
    rec = dict(fields)
    _PLAN_OUTCOMES.append(rec)
    if _LOG_DIR:
        path = os.path.join(_LOG_DIR, PLAN_OUTCOMES_LOG)
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")


def plan_outcomes() -> List[dict]:
    return list(_PLAN_OUTCOMES)


def clear_plan_outcomes() -> None:
    _PLAN_OUTCOMES.clear()
