"""The reduction from a trace to metrics: interval arithmetic on known
intervals, and every trace reader on small traces recorded on a TPU v5e
(``bench/tests/data``), against the numbers they gave when recorded and
against bounds that hold for any trace."""
import json
from pathlib import Path

import pytest

from bench import harness, metrics
from bench import trace as tr
from bench.metrics import Readings, Step
from bench.work import Work

DATA = Path(__file__).resolve().parent / "data"
FIXTURES = sorted(p.stem for p in DATA.glob("*.json"))
TRACE_READERS = ("dot_roofline", "smm_roofline", "device_idle",
                 "exposed_collective")


def test_union_clip_length_subtract():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert tr.union(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.length(iv) == 3.0
    assert tr.clip(iv, (1.5, 3.2)) == [(1.5, 2.0), (3.0, 3.2)]
    assert tr.subtract([(0.0, 10.0)], [(1.0, 2.0), (1.5, 3.0), (9.0, 11.0)]
                       ) == [(0.0, 1.0), (3.0, 9.0)]
    assert tr.subtract([(0.0, 1.0)], [(0.0, 1.0)]) == []


def synthetic():
    ev = tr.Event
    dot = "convolution fusion"
    devices = [[ev("fusion.1 = ... kind=kOutput", 1.0, 2.0, "loop fusion"),
                ev("fusion.3", 2.0, 5.0, dot),
                ev("collective-permute-done", 5.0, 6.0)],
               [ev("fusion.3", 1.0, 4.0, dot),
                ev("collective-permute-done", 3.0, 7.0)]]
    host = [ev(tr.WINDOW, 0.0, 10.0), ev("bench.multiply", 0.0, 1.0),
            ev("bench.wait", 6.0, 10.0)]
    return tr.Trace((0.0, 10.0), devices, host)


def test_readers_on_a_synthetic_trace():
    steps = [Step(0, 0, 0.5, 5, 3, 3), Step(5, 5, 5.5, 10, 3, 3)]
    r = Readings(steps=steps, setup_s=1.0, work=Work(1.0, 0.0),
                 peaks={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                 trace=synthetic(), log=lambda m: None)
    # device 0 busy 5 of 10, device 1 busy 6 of 10
    assert metrics.read("device_idle.dense", r) == pytest.approx(45.0)
    # dot time 3 s on each chip; two steps of 1 s of work at peak
    assert metrics.read("dot_roofline.dense", r) == pytest.approx(
        100.0 * 2 / 3)
    # a collective alone: 1 s on device 0, 3 s on device 1
    assert metrics.read("exposed_collective.dense", r) == pytest.approx(20.0)
    assert metrics.read("smm_roofline.blocked", r) is None
    assert metrics.read("host_return_s.dense", r) == pytest.approx(0.5)
    assert metrics.read("xla_compiles.dense", r) == 3
    assert metrics.read("dense_multiply_s", r) == 5
    bd = harness.breakdown_of(synthetic())
    assert bd["device_ops"][0] == ["fusion.3", 3.0]
    assert dict(bd["idle_gaps"]) == {"bench.multiply": 1.0,
                                     "bench.wait": 3.5}


@pytest.mark.parametrize("name", FIXTURES)
def test_readers_on_a_recorded_chip_trace(name):
    side = json.loads((DATA / f"{name}.json").read_text())
    trace = tr.load(DATA / f"{name}.xplane.pb")
    assert len(trace.devices) == side["chips"]
    steps = [Step(0, 0, 0, 0, 0, 0)] * side["steps"]
    r = Readings(steps=steps, setup_s=0.0, work=Work(**side["work"]),
                 peaks=side["peaks"], trace=trace, log=lambda m: None)
    busy = sum(tr.length(tr.busy(trace, d))
               for d in range(side["chips"])) / side["chips"]
    assert busy == pytest.approx(side["busy_s"], rel=1e-9)
    assert trace.window_s == pytest.approx(side["window_s"], rel=1e-9)
    assert 0 < busy <= trace.window_s
    for family in TRACE_READERS:
        for metric, want in side["metrics"].items():
            if metric.split(".")[0] != family:
                continue
            got = metrics.read(metric, r)
            assert got == pytest.approx(want, rel=1e-9), metric
            assert 0.0 < got <= 100.0, metric
    bd = harness.breakdown_of(trace)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
