"""repro.obs — unified telemetry: spans, metrics, traces, scoreboard.

The observability layer for the multiply pipeline (ISSUE 8):

  spans      ``span("multiply")`` nesting plan, stacks, dispatch,
             finish, plus verify -> repair: profiler annotations
             ``dbcsr.<name>`` on the device trace's clock, kept as
             records under ``enable()`` (telemetry.py), with the JAX
             runtime's lowerings/compiles on the root and dispatch
             (runtime.py)
  metrics    process-wide registry of counters/gauges/histograms that
             the legacy ``stats()`` dicts are thin views over
             (metrics.py)
  readers    the spans of a profiler trace, the JSONL event log, and
             ``python -m repro.obs report`` (export.py, report.py)
  scoreboard predicted-vs-actual planner cost per executed algorithm,
             consumed by ``planner.calibrate --check-drift``
             (scoreboard.py)

Contract (mirrors PR 7's ``verify=None``): telemetry is OFF by
default, and when off the multiply paths are bit-identical and add
zero registry entries — spans are then inactive profiler annotations
with no record, timing or synchronisation.  Explicit publishers
(service counters, ``plan_cache_stats()``) use the registry as their
storage even when tracing is off; that is their data living in one
place, not overhead.

Typical use::

    from repro import obs
    obs.enable(log_dir="artifacts/obs")
    with jax.profiler.trace("artifacts/obs/profile"):
        c, plan = dbcsr.multiply(a, b, mesh=mesh, return_plan=True)
    print(obs.render_timeline(obs.profile_spans("artifacts/obs/profile")))
    print(obs.render_scoreboard(
        obs.planner_scoreboard(obs.plan_outcomes())))

This package imports nothing from ``repro.core``/``repro.planner``
(they import us) and no jax at import time — it is safe at any layer.
"""
from .telemetry import (  # noqa: F401
    SpanRecord, Tracer, NOOP_SPAN, enable, disable, enabled,
    span, maybe_span, last_trace, record_plan_outcome,
    plan_outcomes, clear_plan_outcomes, EVENTS_LOG, PLAN_OUTCOMES_LOG,
    PROFILE_PREFIX,
)
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, REGISTRY, registry,
    counter, gauge, histogram, metrics_snapshot, clear_metrics,
)
from .export import (  # noqa: F401
    profile_spans, write_jsonl, read_jsonl,
)
from .scoreboard import (  # noqa: F401
    planner_scoreboard, render_scoreboard, check_drift,
)
from .report import (  # noqa: F401
    category_breakdown, render_breakdown, render_timeline,
)

__all__ = [
    "SpanRecord", "Tracer", "NOOP_SPAN", "enable", "disable", "enabled",
    "span", "maybe_span", "last_trace",
    "record_plan_outcome", "plan_outcomes", "clear_plan_outcomes",
    "EVENTS_LOG", "PLAN_OUTCOMES_LOG", "PROFILE_PREFIX",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "registry", "counter", "gauge", "histogram", "metrics_snapshot",
    "clear_metrics",
    "profile_spans", "write_jsonl", "read_jsonl",
    "planner_scoreboard", "render_scoreboard", "check_drift",
    "category_breakdown", "render_breakdown", "render_timeline",
]
