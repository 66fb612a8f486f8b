"""Telemetry battery: metrics registry, span nesting, profiler spans
and the JAX runtime's counters on them, the disabled-path contract, and
predicted-vs-actual planner accounting (repro.obs).

The hard contract under test: with telemetry DISABLED (the default)
the multiply paths are bitwise identical to an enabled-then-disabled
process, add ZERO registry entries, never wait on the device and run
no planner they did not run before; under a profiler session one
``dbcsr.multiply`` leaves nested ``dbcsr.*`` host events carrying the
call's lowerings and compiles; with telemetry ENABLED it leaves a
well-formed span tree (multiply > plan, stacks, dispatch, finish) and
records a predicted-vs-measured plan outcome for the scoreboard.
"""
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402
from repro.compat import make_mesh  # noqa: E402
from repro.core import dbcsr  # noqa: E402
from repro.core.blocking import GridSpec  # noqa: E402
from repro.core.multiply import distributed_matmul  # noqa: E402

EXEC_KW = dict(algorithm="cannon", densify=False, local_kernel="ref",
               pipeline_depth=1)


@pytest.fixture()
def rng():
    """Module-local stream: this file must NOT consume the session-scoped
    conftest rng — later test files' data depends on its position."""
    return np.random.RandomState(0)


def _reset_obs():
    obs.enable()   # reset=True installs a fresh, empty tracer ...
    obs.disable()  # ... and the default state is OFF
    obs.clear_metrics()
    obs.clear_plan_outcomes()


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with telemetry off and empty stores."""
    _reset_obs()
    yield
    _reset_obs()


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _operand(rng, m, n, *, block=32, mesh=None):
    return dbcsr.create(rng.randn(m, n).astype(np.float32), mesh=mesh,
                        block_size=block)


def _spans_by_name(spans, name):
    return [s for s in spans if s.name == name]


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def _profiled(log_dir, fn):
    """``fn()`` under a profiler session writing to ``log_dir``; its
    value and the ``dbcsr.*`` spans of the trace."""
    with jax.profiler.trace(str(log_dir)):
        out = fn()
        jax.block_until_ready(getattr(out, "data", out))
    return out, obs.profile_spans(str(log_dir))


# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------


def test_counter_inc_and_negative_rejected():
    c = obs.counter("t.count")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same instance
    assert obs.counter("t.count") is c


def test_labels_isolate_series():
    a = obs.counter("t.lbl", algo="cannon")
    b = obs.counter("t.lbl", algo="summa")
    a.inc(3)
    assert b.value == 0 and a.value == 3
    # label order must not matter
    assert obs.counter("t.two", x="1", y="2") is obs.counter(
        "t.two", y="2", x="1")


def test_gauge_keeps_sample_history():
    g = obs.gauge("t.occ")
    for v in (0.2, 0.9, 0.4):
        g.set(v)
    assert g.value == 0.4
    assert g.samples == [0.2, 0.9, 0.4]


def test_histogram_percentiles_match_numpy():
    h = obs.histogram("t.lat")
    rng = np.random.RandomState(7)
    vals = rng.rand(101).tolist()
    for v in vals:
        h.observe(v)
    for p in (50, 90, 99):
        assert h.percentile(p) == pytest.approx(
            float(np.percentile(np.asarray(vals), p)), abs=1e-12)
    assert h.count == 101
    # empty histogram is defined (the service's zero-request case)
    assert obs.histogram("t.empty").percentile(99) == 0.0


def test_registry_snapshot_and_clear():
    obs.counter("t.a").inc()
    obs.gauge("t.b").set(1.0)
    obs.histogram("t.c").observe(2.0)
    assert len(obs.registry()) == 3
    snap = obs.metrics_snapshot()
    assert len(snap) == 3
    obs.clear_metrics()
    assert len(obs.registry()) == 0


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------


def test_span_nesting_and_last_trace():
    tracer = obs.enable()
    with obs.span("outer", cat="multiply"):
        with obs.span("inner", cat="plan") as sp:
            sp.set(algorithm="cannon")
    outer = _spans_by_name(tracer.spans, "outer")[0]
    inner = _spans_by_name(tracer.spans, "inner")[0]
    assert inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id == outer.span_id
    assert inner.attrs["algorithm"] == "cannon"
    assert {s.name for s in obs.last_trace()} == {"outer", "inner"}


def test_span_disabled_is_shared_noop():
    # a call site under jit tracing gets the shared no-op span
    assert obs.maybe_span(False, "x") is obs.NOOP_SPAN
    with obs.maybe_span(False, "x") as sp:
        sp.set(ignored=1)
    # telemetry off: a live span is an annotation and keeps no record
    with obs.span("x", counters=True) as sp:
        sp.set(ignored=1)
    assert sp.rec is None
    assert obs.last_trace() == []
    assert len(obs.registry()) == 0


def test_span_exception_tagged_and_stack_recovers():
    tracer = obs.enable()
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    rec = _spans_by_name(tracer.spans, "boom")[0]
    assert rec.attrs["error"] == "RuntimeError"
    assert tracer.current() is None  # stack popped despite the raise


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _toy_trace():
    tracer = obs.enable()
    with obs.span("root", cat="multiply"):
        with obs.span("child", cat="plan"):
            pass
    return obs.last_trace()


def test_profile_spans_nest_and_carry_metadata(tmp_path):
    # only dbcsr.* annotations are read back, nested by containment on
    # their thread; a counting span carries the runtime's counts
    def fn():
        with jax.profiler.TraceAnnotation("other"):
            with obs.span("root", counters=True):
                with obs.span("child"):
                    pass
                return jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(3))

    _, spans = _profiled(tmp_path, fn)
    root, child = sorted(spans, key=lambda s: s.t0)
    assert (root.name, child.name) == ("dbcsr.root", "dbcsr.child")
    assert root.parent_id is None and child.parent_id == root.span_id
    assert root.t0 <= child.t0 and child.t0 + child.dur <= root.t0 + root.dur
    assert child.attrs == {}
    assert root.attrs["lowerings"] >= 1 and root.attrs["compiles"] >= 1
    assert "lambda" in root.attrs["lowered"]
    assert "dbcsr.child" in obs.render_timeline(spans)


def test_runtime_counts_lowerings_and_publishes_only_when_enabled():
    from jax import monitoring
    from repro.obs import runtime

    runtime.install()
    mark = runtime.mark()
    monitoring.record_event_duration_secs(runtime.LOWER, 0.25,
                                          fun_name="f,g#h")
    monitoring.record_event_duration_secs(runtime.LOWER, 0.5,
                                          fun_name="f,g#h")
    monitoring.record_event_duration_secs(runtime.TRACE, 0.125,
                                          fun_name="f")
    monitoring.record_event_duration_secs(runtime.COMPILE, 1.0,
                                          fun_name="f")
    monitoring.record_event(runtime.CACHE_HIT)
    counts = runtime.since(mark)
    assert counts == {"traces": 1, "lowerings": 2, "compiles": 1,
                      "cache_hits": 1, "trace_s": pytest.approx(0.125),
                      "lower_s": pytest.approx(0.75),
                      "compile_s": pytest.approx(1.0), "lowered": "f g h"}
    assert len(obs.registry()) == 0   # telemetry off: counted, not published
    obs.enable()
    mark = runtime.mark()
    for i in range(40):
        monitoring.record_event_duration_secs(runtime.LOWER, 0.0,
                                              fun_name=f"program_{i}")
    counts = runtime.since(mark)
    assert len(counts["lowered"]) == runtime.NAMES_MAX
    assert counts["lowered"].startswith("program_0;program_1;")
    assert obs.counter("jax.lowerings").value == 40


def test_jsonl_event_log_round_trip(tmp_path):
    log_dir = str(tmp_path / "obs")
    obs.enable(log_dir=log_dir)
    with obs.span("root", cat="multiply"):
        pass
    obs.record_plan_outcome(algorithm="cannon", predicted_s=1.0,
                            measured_s=2.0)
    events = obs.read_jsonl(os.path.join(log_dir, obs.EVENTS_LOG))
    outcomes = obs.read_jsonl(os.path.join(log_dir, obs.PLAN_OUTCOMES_LOG))
    assert [e["name"] for e in events] == ["root"]
    assert outcomes == [{"algorithm": "cannon", "predicted_s": 1.0,
                         "measured_s": 2.0}]
    # round-trip through SpanRecord for the report CLI
    rec = obs.SpanRecord.from_dict(events[0])
    assert rec.name == "root" and rec.dur >= 0
    assert obs.read_jsonl(str(tmp_path / "missing.jsonl")) == []


def test_report_cli(tmp_path, capsys):
    from repro.obs import report

    log_dir = str(tmp_path / "obs")
    assert report.main(["--dir", log_dir]) == 1  # no logs yet
    capsys.readouterr()
    obs.enable(log_dir=log_dir)
    with obs.span("multiply", cat="multiply"):
        with obs.span("plan", cat="plan"):
            pass
    obs.record_plan_outcome(algorithm="cannon", predicted_s=1.0,
                            measured_s=2.0)
    obs.disable()
    assert report.main(["--dir", log_dir, "--timeline"]) == 0
    out = capsys.readouterr().out
    assert "plan" in out and "cannon" in out and "scoreboard" in out


# ---------------------------------------------------------------------------
# the zero-overhead-off contract
# ---------------------------------------------------------------------------


def test_disabled_is_bitwise_identical_and_adds_no_metrics(rng, tmp_path):
    mesh = _mesh11()
    a = _operand(rng, 128, 128, mesh=mesh)
    b = _operand(rng, 128, 128, mesh=mesh)
    kw = dict(mesh=mesh, **EXEC_KW)

    obs.clear_metrics()
    c_off = dbcsr.multiply(a, b, **kw)
    jax.block_until_ready(c_off.data)
    assert len(obs.registry()) == 0, \
        "disabled multiply must add zero registry entries"
    assert obs.last_trace() == []

    # a profiler session sees the spans, and changes neither the
    # product nor the registry
    c_prof, spans = _profiled(tmp_path, lambda: dbcsr.multiply(a, b, **kw))
    assert spans and len(obs.registry()) == 0
    assert obs.last_trace() == []

    obs.enable()
    c_on = dbcsr.multiply(a, b, **kw)
    jax.block_until_ready(c_on.data)
    obs.disable()
    c_off2 = dbcsr.multiply(a, b, **kw)

    assert (np.asarray(c_on.data) == np.asarray(c_off.data)).all()
    assert (np.asarray(c_off2.data) == np.asarray(c_off.data)).all()
    assert (np.asarray(c_prof.data) == np.asarray(c_off.data)).all()


def test_disabled_never_waits_nor_plans_a_pinned_call(rng, tmp_path,
                                                      monkeypatch):
    # telemetry off, with or without a profiler session: the dispatch
    # only enqueues, and a pinned call runs no planner; telemetry on:
    # the dispatch waits and the planner prices the call for the
    # scoreboard, as before
    import repro.planner.plan as planner

    calls = {"wait": 0, "plan": 0}
    wait, plan_multiply = jax.block_until_ready, planner.plan_multiply

    def counted_wait(x):
        calls["wait"] += 1
        return wait(x)

    def counted_plan(*args, **kwargs):
        calls["plan"] += 1
        return plan_multiply(*args, **kwargs)

    mesh = _mesh11()
    grid = GridSpec("data", "model")
    A = jnp.asarray(rng.randn(64, 64).astype(np.float32))
    B = jnp.asarray(rng.randn(64, 64).astype(np.float32))

    def call():
        return distributed_matmul(A, B, mesh=mesh, grid=grid, block_m=32,
                                  block_k=32, block_n=32, **EXEC_KW)

    ref = wait(call())
    monkeypatch.setattr(jax, "block_until_ready", counted_wait)
    monkeypatch.setattr(planner, "plan_multiply", counted_plan)
    assert (np.asarray(call()) == np.asarray(ref)).all()
    with jax.profiler.trace(str(tmp_path)):
        assert (np.asarray(call()) == np.asarray(ref)).all()
    assert calls == {"wait": 0, "plan": 0}
    obs.enable()
    call()
    assert calls["wait"] >= 1 and calls["plan"] == 1
    assert len(obs.plan_outcomes()) == 1


def test_enabled_under_jit_records_nothing(rng, tmp_path):
    # operands are jax tracers under jit: the per-call flag must veto
    # spans — records and profiler annotations — even though the global
    # switch is on and a profiler session runs
    mesh = _mesh11()
    grid = GridSpec("data", "model")
    A = rng.randn(64, 64).astype(np.float32)
    B = rng.randn(64, 64).astype(np.float32)
    tracer = obs.enable()

    fn = jax.jit(lambda x, y: distributed_matmul(
        x, y, mesh=mesh, grid=grid, block_m=32, block_k=32, block_n=32,
        **EXEC_KW))
    with jax.profiler.trace(str(tmp_path)):
        C = jax.block_until_ready(fn(A, B))
    np.testing.assert_allclose(np.asarray(C), A @ B, rtol=2e-4, atol=2e-4)
    assert tracer.spans == []
    assert obs.plan_outcomes() == []
    assert obs.profile_spans(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# traced multiply: span tree, durations, plan outcome
# ---------------------------------------------------------------------------


def test_traced_multiply_span_tree_and_outcome(rng):
    mesh = _mesh11()
    a = _operand(rng, 128, 128, mesh=mesh)
    b = _operand(rng, 128, 128, mesh=mesh)
    obs.enable()
    c, plan = dbcsr.multiply(a, b, mesh=mesh, return_plan=True, **EXEC_KW)
    jax.block_until_ready(c.data)
    obs.disable()

    spans = obs.last_trace()
    (root,) = [s for s in spans if s.parent_id is None]
    assert root.name == "multiply" and root.cat == "multiply"
    kids = {s.name: s for s in _children(spans, root)}
    assert set(kids) == {"plan", "stacks", "dispatch", "finish"}
    assert len(spans) == 5   # the phases have no children of their own
    assert kids["plan"].attrs["algorithm"] == "cannon"
    disp = kids["dispatch"]
    assert disp.attrs["comm_bytes"] >= 0
    # the dispatch and the root carry the runtime's counters over their
    # intervals; the root's include the dispatch's
    for key in ("lowerings", "traces", "compiles", "cache_hits"):
        assert root.attrs[key] >= disp.attrs[key] >= 0
    assert root.attrs["lower_s"] >= disp.attrs["lower_s"] >= 0.0
    assert isinstance(disp.attrs["lowered"], str)
    # the phases run one after another inside the root
    order = sorted(kids.values(), key=lambda s: s.t0)
    assert [s.name for s in order] == ["plan", "stacks", "dispatch",
                                       "finish"]
    for s, t in zip(order, order[1:]):
        assert s.t0 + s.dur <= t.t0
    assert root.dur >= sum(s.dur for s in order) > 0
    # the per-step model stays on the plan, not in the trace
    steps = plan.schedule_stats["steps"]
    assert steps and all("flops" in s and "comm_bytes" in s
                         and "occupancy" in s for s in steps)

    # every traced non-trivial multiply records predicted-vs-measured
    (out,) = obs.plan_outcomes()
    assert out["algorithm"] == "cannon"
    assert out["predicted_s"] == pytest.approx(float(plan.predicted_s))
    assert 0 < out["measured_s"] <= disp.dur


def test_profiled_multiply_span_tree_and_counters(rng, tmp_path):
    # telemetry off: the profiler alone sees the phases nested on its
    # clock, and the first call's root names what it lowered
    mesh = _mesh11()
    a = _operand(rng, 96, 96, mesh=mesh)
    b = _operand(rng, 96, 96, mesh=mesh)
    _, spans = _profiled(tmp_path, lambda: dbcsr.multiply(
        a, b, mesh=mesh, **EXEC_KW))
    (root,) = [s for s in spans if s.parent_id is None]
    assert root.name == "dbcsr.multiply"
    kids = [s.name for s in sorted(_children(spans, root),
                                   key=lambda s: s.t0)]
    assert kids == ["dbcsr.plan", "dbcsr.stacks", "dbcsr.dispatch",
                    "dbcsr.finish"]
    (disp,) = _spans_by_name(spans, "dbcsr.dispatch")
    assert root.attrs["lowerings"] >= disp.attrs["lowerings"] >= 1
    assert root.attrs["compiles"] >= 1
    assert root.attrs["lower_s"] > 0.0
    assert root.attrs["lowered"] and disp.attrs["lowered"]
    assert set(disp.attrs["lowered"].split(";")) <= set(
        root.attrs["lowered"].split(";"))
    assert obs.last_trace() == [] and len(obs.registry()) == 0


def test_traced_fused_batched_span_tree(rng):
    mesh = _mesh11()
    pairs = [(_operand(rng, 64, 64, mesh=mesh),
              _operand(rng, 64, 64, mesh=mesh)) for _ in range(3)]
    obs.enable()
    out = dbcsr.multiply_batched(pairs, mesh=mesh, fused=True,
                                 **EXEC_KW)
    jax.block_until_ready(out[0].data)
    obs.disable()

    spans = obs.last_trace()
    (root,) = [s for s in spans if s.parent_id is None]
    assert root.name == "multiply_batched"
    assert root.attrs["n_groups"] == 3
    kids = {s.name: s for s in _children(spans, root)}
    assert set(kids) == {"plan", "stacks", "dispatch", "finish"}
    disp = kids["dispatch"]
    assert disp.attrs["n_groups"] == 3 and disp.attrs["comm_bytes"] >= 0
    assert root.attrs["compiles"] >= disp.attrs["compiles"] >= 0
    assert not _children(spans, disp)
    # ONE fused dispatch — no nested per-request "multiply" roots
    assert _spans_by_name(spans, "multiply") == []
    # fuse-or-loop decision counters (gated, enabled here)
    assert obs.counter("batched.requests_fused").value == 3
    assert obs.counter("batched.requests_looped").value == 0
    (bout,) = obs.plan_outcomes()
    assert bout["kind"] == "multiply_batched" and bout["fuse"] is True


def test_traced_abft_repair_nests_second_dispatch(rng):
    from repro.robustness import chaos
    from repro.sparsity.norms import compute_block_norms

    mesh = _mesh11()
    a = _operand(rng, 128, 128, mesh=mesh)
    b = _operand(rng, 128, 128, mesh=mesh)
    kw = dict(mesh=mesh, verify="checksum", **EXEC_KW)
    clean = dbcsr.multiply(a, b, mesh=mesh, **EXEC_KW)

    norms = compute_block_norms(clean.data, 32, 32)
    i0, j0 = np.unravel_index(int(np.argmax(norms)), norms.shape)
    hook = chaos.FaultInjector(seed=7).one_shot_result_hook(
        int(i0), int(j0), block_m=32, block_n=32, mode="bitflip")

    obs.enable()
    with chaos.result_corruption(hook):
        cr = dbcsr.multiply(a, b, **kw)
    obs.disable()
    assert (np.asarray(cr.data) == np.asarray(clean.data)).all()

    spans = obs.last_trace()
    (root,) = [s for s in spans if s.parent_id is None]
    (verify,) = _spans_by_name(spans, "verify")
    assert verify.parent_id == root.span_id
    assert verify.attrs == {**verify.attrs, "detected": True,
                            "repaired": True, "n_flagged_blocks": 1}
    (repair,) = _spans_by_name(spans, "repair")
    assert repair.parent_id == verify.span_id
    # the repair re-execution shows up as a SECOND dispatch span,
    # nested under repair (the first is the corrupted original)
    dispatches = _spans_by_name(spans, "dispatch")
    assert len(dispatches) == 2
    assert sorted(d.parent_id for d in dispatches) == sorted(
        [root.span_id, repair.span_id])
    # ABFT registry counters (gated, enabled here)
    assert obs.counter("abft.detections").value == 1
    assert obs.counter("abft.repairs").value == 1
    # measured_s is the FIRST (pre-repair) dispatch, not the re-run
    (out,) = obs.plan_outcomes()
    first = min(dispatches, key=lambda s: s.t0)
    assert out["measured_s"] == pytest.approx(first.dur, rel=0.25)


def test_local_dot_named_in_its_op_metadata():
    # where the densified local multiply is traced into one program, its
    # dot carries a stable name for the device trace
    from repro.core.densify import densified_local_matmul

    x = jnp.ones((8, 8), jnp.float32)
    text = jax.jit(densified_local_matmul()).lower(x, x).as_text(
        debug_info=True)
    assert "dbcsr.local_dot/dot_general" in text


# ---------------------------------------------------------------------------
# scoreboard + drift
# ---------------------------------------------------------------------------


def _mk_records():
    return [
        {"algorithm": "cannon", "predicted_s": 1.0, "measured_s": 1.1},
        {"algorithm": "cannon", "predicted_s": 0.9, "measured_s": 1.0},
        {"algorithm": "summa", "predicted_s": 5.0, "measured_s": 1.0},
        {"algorithm": "broken", "predicted_s": 1.0, "measured_s": 0.0},
    ]


def test_planner_scoreboard_fields():
    sb = obs.planner_scoreboard(_mk_records())
    assert set(sb) == {"cannon", "summa"}  # zero-measurement row skipped
    assert sb["cannon"]["n"] == 2
    # rel errs: (1.0-1.1)/1.1 and (0.9-1.0)/1.0 -> median is their mean
    assert sb["cannon"]["rel_err_median"] == pytest.approx(
        (-0.1 / 1.1 - 0.1) / 2.0, abs=1e-12)
    assert sb["summa"]["rel_err_median"] == pytest.approx(4.0)
    assert "cannon" in obs.render_scoreboard(sb)


def test_check_drift_flags_and_min_samples():
    res = obs.check_drift(_mk_records(), threshold=1.0)
    assert not res["ok"] and list(res["flagged"]) == ["summa"]
    ok = obs.check_drift(_mk_records(), threshold=10.0)
    assert ok["ok"] and ok["flagged"] == {}
    # below min_samples: reported but never flagged
    res2 = obs.check_drift(_mk_records(), threshold=1.0, min_samples=2)
    assert res2["ok"] and "summa" in res2["scoreboard"]


def test_calibrate_drift_report_reads_log(tmp_path):
    from repro.planner import calibrate

    path = str(tmp_path / "plan_outcomes.jsonl")
    with open(path, "w") as f:
        for r in _mk_records():
            f.write(json.dumps(r) + "\n")
    rep = calibrate.drift_report(path, threshold=1.0)
    assert not rep["ok"] and "summa" in rep["flagged"]
    assert rep["n_records"] == 4 and rep["path"] == path
    # a missing log is not drift (advisory default)
    empty = calibrate.drift_report(str(tmp_path / "nope.jsonl"))
    assert empty["ok"] and empty["n_records"] == 0


# ---------------------------------------------------------------------------
# legacy stats() dicts as registry views
# ---------------------------------------------------------------------------


def test_plan_cache_stats_is_registry_view():
    from repro.planner.plan import plan_cache_clear, plan_cache_stats, \
        plan_multiply

    plan_cache_clear()
    plan_multiply(256, 256, 256, mesh_shape=(1, 1))
    plan_multiply(256, 256, 256, mesh_shape=(1, 1))
    st = plan_cache_stats()
    assert set(st) == {"hits", "misses", "currsize", "maxsize",
                       "evictions"}
    assert st["hits"] >= 1 and st["misses"] >= 1
    # the dict is a view over registry gauges, not a second counter
    for key, val in st.items():
        assert obs.gauge(f"planner.plan_cache.{key}").value == val


def test_service_stats_is_registry_view(rng):
    from repro.serve.multiply_service import MultiplyService

    mesh = _mesh11()
    svc = MultiplyService(mesh, slo_s=0.0, max_batch=8, **EXEC_KW)
    other = MultiplyService(mesh, slo_s=0.0, max_batch=8, **EXEC_KW)
    assert svc.service_id != other.service_id
    t = [svc.submit(_operand(rng, 64, 64, mesh=mesh),
                    _operand(rng, 64, 64, mesh=mesh)) for _ in range(2)]
    svc.flush()
    for ti in t:
        svc.result(ti)
    st = svc.stats()
    assert st["n_requests"] == 2 and st["n_completed"] == 2
    assert st["latency_p99_s"] >= st["latency_p50_s"] > 0
    # the registry is the storage, labeled per instance
    assert obs.counter("service.requests",
                       service=svc.service_id).value == 2
    assert obs.counter("service.requests",
                       service=other.service_id).value == 0
    assert other.stats()["n_requests"] == 0
    assert obs.histogram("service.latency_s",
                         service=svc.service_id).count == 2


def test_executor_stats_publish_only_when_enabled(rng):
    from repro.core import engine

    obs.clear_metrics()
    p = engine.build_executor_plan(128, 128, 128, 4, 4, 4, 32)
    p.stats()
    assert len(obs.registry()) == 0  # gated: off by default
    obs.enable()
    st = p.stats()
    obs.disable()
    assert obs.counter("executor.stats_reports").value == 1
    assert obs.counter("executor.entries").value == st["n_entries"]
    assert obs.histogram("executor.occupancy").count == 1
