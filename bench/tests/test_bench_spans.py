"""The readers of the program's own spans (``bench/spans.py`` and the
metrics ``plan_s``, ``dispatch_s``, ``lower_s``, ``dispatch_idle``): on a
hand-built trace, on a small profiler trace recorded here on the CPU, and
on a trace of real multiplies recorded the same way."""
from pathlib import Path

import numpy as np
import pytest

from bench import harness, metrics, spans
from bench import trace as tr
from bench.metrics import Readings, Step
from bench.work import Work

READERS = ("plan_s.dense", "dispatch_s.dense", "lower_s.dense",
           "dispatch_idle.dense")


def readings(trace, n_steps=2):
    steps = [Step(0, 0, 0, 0, 0, 0)] * n_steps
    return Readings(steps=steps, setup_s=0.0, work=Work(1.0, 0.0),
                    peaks={}, trace=trace, log=lambda m: None)


def record(log_dir: Path, body) -> Path:
    """Run ``body()`` inside ``bench.window`` under a CPU profiler
    session; the ``.xplane.pb`` it wrote."""
    import jax

    with jax.profiler.trace(str(log_dir)):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            body()
    return tr.find(log_dir)


def host_trace(path: Path, devices) -> tr.Trace:
    """The host part of ``tr.load``, for a trace with no TPU plane."""
    from jax.profiler import ProfileData

    host = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                events = [tr.Event(e.name, e.start_ns * 1e-9,
                                   e.end_ns * 1e-9) for e in line.events]
                if any(e.name == tr.WINDOW for e in events):
                    host += events
    (window,) = [(e.start, e.end) for e in host if e.name == tr.WINDOW]
    return tr.Trace(window, devices, host)


def spanned():
    """Two steps in a 10 s window: the program's phases on the host, and
    what the two chips ran."""
    ev = tr.Event
    host = [ev(tr.WINDOW, 0.0, 10.0),
            ev("dbcsr.multiply", 0.0, 4.0), ev("dbcsr.plan", 0.0, 0.5),
            ev("dbcsr.dispatch", 0.5, 3.5), ev("dbcsr.finish", 3.5, 4.0),
            ev("dbcsr.multiply", 5.0, 9.0), ev("dbcsr.plan", 5.0, 5.25),
            ev("dbcsr.dispatch", 5.25, 8.25), ev("dbcsr.finish", 8.25, 9.0),
            # outside the window: not counted
            ev("dbcsr.dispatch", 10.5, 11.0)]
    devices = [[ev("fusion.3", 3.0, 4.5), ev("fusion.3", 8.0, 9.5)],
               [ev("fusion.3", 1.5, 3.5)]]
    return tr.Trace((0.0, 10.0), devices, host)


def test_span_readers_on_a_hand_built_trace():
    r = readings(spanned())
    assert metrics.read("plan_s.dense", r) == pytest.approx(0.375)
    assert metrics.read("dispatch_s.dense", r) == pytest.approx(3.0)
    # chip 0 idle in dispatch: 2.5 + 2.75 s; chip 1: 1.0 + 3.0 s
    assert metrics.read("dispatch_idle.dense", r) == pytest.approx(
        100.0 * (5.25 + 4.0) / 2 / 10.0)
    assert metrics.read("dispatch_idle.dense", r) <= metrics.read(
        "device_idle.dense", r)


@pytest.mark.parametrize("name", READERS)
def test_span_readers_find_nothing_without_program_spans(name, tmp_path,
                                                         monkeypatch):
    # the parent commit's program opens no dbcsr.* span
    path = record(tmp_path, lambda: None)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    r = readings(host_trace(path, [[tr.Event("fusion.3", 0.0, 1e-9)]]))
    assert metrics.read(name, r) is None
    assert metrics.read(name, readings(None)) is None


def test_metadata_from_a_recorded_trace(tmp_path, monkeypatch):
    import jax

    def multiply(lower_s):
        with jax.profiler.TraceAnnotation("dbcsr.multiply") as ann:
            with jax.profiler.TraceAnnotation("dbcsr.dispatch"):
                pass
            ann.set_metadata(lowerings=8, lower_s=lower_s,
                             lowered="jit(a);jit(b)")

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            multiply(0.25)
            multiply(0.5)
        multiply(9.0)   # after the window: left out
    trace = host_trace(tr.find(tmp_path), [[]])
    got = spans.metadata(trace.window, "dbcsr.multiply", tmp_path)
    assert got == [{"lowerings": 8, "lower_s": 0.25,
                    "lowered": "jit(a);jit(b)"},
                   {"lowerings": 8, "lower_s": 0.5,
                    "lowered": "jit(a);jit(b)"}]
    assert spans.metadata(trace.window, "dbcsr.dispatch", tmp_path) == \
        [{}, {}]
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    assert metrics.read("lower_s.dense", readings(trace, 3)) == \
        pytest.approx(0.25)


def test_span_readers_on_profiled_multiplies(tmp_path, monkeypatch):
    # the program's spans as the benchmark's readers see them: a fresh
    # operand per step, as in the timed window
    import jax
    from repro.compat import make_mesh
    from repro.core import dbcsr

    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.RandomState(0)
    a = dbcsr.create(rng.randn(88, 88).astype(np.float32), mesh=mesh,
                     block_size=22)
    dbcsr.multiply(a, a, mesh=mesh, densify=True)   # warm-up

    def body():
        for s in (0.9, 1.1):
            c = dbcsr.multiply(a.scale(s), a, mesh=mesh, densify=True)
            jax.block_until_ready(c.data)

    path = record(tmp_path, body)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    r = readings(host_trace(path, [[]]))
    got = {name: metrics.read(name, r) for name in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["lower_s.dense"] <= got["dispatch_s.dense"]
    assert got["plan_s.dense"] < got["dispatch_s.dense"]
    # no device ops recorded: the chip is idle all through the dispatch
    assert got["dispatch_idle.dense"] == pytest.approx(
        100.0 * 2 * got["dispatch_s.dense"] / r.trace.window_s)
