"""The four-chip cell on the CPU: a run of its configuration's path on
four virtual devices is correct, and not correct with the control in the
program's place; ``exposed_transfer`` on a trace whose transfers overlap
each other and the dot; and ``comm_bytes`` on spans with and without the
program's count.  Nothing here touches a chip."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import harness, metrics
from bench import trace as tr
from bench.metrics import Readings, Step
from bench.work import Work

CELL = "dense-n33792-2x2"
N = 22 * 16
PEAKS = harness.peaks_for("TPU v5 lite")

RUN = r"""
import dataclasses, json, sys
import jax
from bench import harness, reference
from repro.core import dbcsr

cell = harness.load_cell(sys.argv[1])
pin = json.loads(sys.argv[3])
cell.config = dict(cell.config, m=int(sys.argv[2]), k=int(sys.argv[2]),
                   n=int(sys.argv[2]),
                   multiply=dict(cell.config["multiply"], **pin))


def run():
    result = harness.run_cell(
        cell, seed=2 ** 33 + 15, seconds=0.01, traced=False,
        devices=jax.devices()[:cell.chips],
        peaks=harness.peaks_for("TPU v5 lite"), t_process=harness.clock(),
        log=lambda msg: None)
    return {k: result[k] for k in ("correct", "attempted", "failed",
                                   "checks", "metrics")}


multiply = dbcsr.multiply


def control(a, b, **kw):
    # the control in the program's place: the reference's product of the
    # same operands at the precision below
    c = multiply(a, b, **kw)
    return dataclasses.replace(c, data=reference.control(a.data, b.data))


out = {"sound": run()}
dbcsr.multiply = control
out["control"] = run()
print(json.dumps(out))
"""


@pytest.fixture(scope="module", params=[{}, {"algorithm": "cannon"}],
                ids=["auto", "cannon"])
def mesh_runs(request):
    """A sound run and a control run of the cell's configuration at
    order ``N`` on four virtual CPU devices, in a subprocess; as the
    configuration is (``auto``), and with Cannon pinned, the path the
    planner picks at the cell's order."""
    root = str(harness.ROOT)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", RUN, CELL, str(N), json.dumps(request.param)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_run_is_correct(mesh_runs):
    sound = mesh_runs["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert set(sound["metrics"]) == {
        name for name, _ in harness.load_cell(CELL).end_to_end}


def test_control_in_place_is_not_correct(mesh_runs):
    control = mesh_runs["control"]
    assert not control["correct"], control["checks"]
    assert any(c["value"] > c["limit"] for c in control["checks"].values())


def ops(t0, q):
    """One chip's ops in one step of ``20 q``: the skew's A and B
    permutes overlapping each other for ``2 q`` with nothing else
    running, the first dot on their results (which names the ``-done``
    ops as operands) for ``10 q``, and the shift's pair started with the
    dot and done under it."""
    ev = tr.Event
    skew_end = t0 + 2 * q
    return [
        ev("%collective-permute-start = (bf16[2]) collective-permute-start("
           "bf16[2] %convert)", t0, t0 + 1e-6),
        ev("%collective-permute-start.1 = (bf16[2]) collective-permute-"
           "start(bf16[2] %convert.1)", t0 + 1e-6, t0 + 2e-6),
        ev("%collective-permute-done = bf16[2] collective-permute-done("
           "(bf16[2]) %collective-permute-start)", skew_end - 1e-6,
           skew_end),
        ev("%collective-permute-done.1 = bf16[2] collective-permute-done("
           "(bf16[2]) %collective-permute-start.1)", skew_end - 1e-6,
           skew_end),
        ev("%collective-permute-start.2 = (bf16[2]) collective-permute-"
           "start(bf16[2] %collective-permute-done)", skew_end,
           skew_end + 1e-6),
        ev("%fusion = f32[2] fusion(bf16[2] %collective-permute-done, "
           "bf16[2] %collective-permute-done.1), kind=kOutput", skew_end,
           skew_end + 10 * q, "convolution fusion"),
        ev("%collective-permute-done.2 = bf16[2] collective-permute-done("
           "(bf16[2]) %collective-permute-start.2)",
           skew_end + 2 * q - 1e-6, skew_end + 2 * q),
    ]


def mesh_readings(q=1e-3, steps=3, chips=4, extra=()):
    step_s = 20 * q
    devices = [sum((ops(1.0 + s * step_s, q) for s in range(steps)),
                   list(extra)) for _ in range(chips)]
    host = [tr.Event(tr.WINDOW, 1.0, 1.0 + steps * step_s)]
    trace = tr.Trace((1.0, 1.0 + steps * step_s), devices, host)
    return Readings(steps=[Step(0, 0, 0, 0, 0, 0)] * steps, setup_s=0.0,
                    work=Work(1.0, 0.0), peaks=PEAKS, trace=trace,
                    log=lambda m: None)


def test_exposed_transfer_reads_what_nothing_hides():
    # per step the skew's 2 q runs alone and the shift hides under the
    # dot: 2 q of 20 q
    r = mesh_readings()
    assert metrics.read("exposed_transfer.dense", r) == pytest.approx(10.0)


def test_a_dot_on_done_operands_is_no_transfer():
    from bench.metrics import exposed_transfer as et

    moving, rest, names = et.transfers(mesh_readings().trace.devices[0])
    assert len(moving) == 9 and len(rest) == 3
    assert not any("fusion" in n for n in names)
    assert et.own_name("%fusion = f32[2] fusion(bf16[2] "
                       "%collective-permute-done)") == "fusion"


@pytest.mark.parametrize("op,share", [
    # a synchronous collective is its own interval: 1 q alone in 60 q
    (("%all-reduce = f32[2] all-reduce(f32[2] %p)", 1.0 + 60e-3 - 1e-3,
      1.0 + 60e-3), 10.0 + 100 / 60),
    # a -done whose start the trace does not hold is left out
    (("%collective-permute-done.9 = bf16[2] collective-permute-done("
      "(bf16[2]) %collective-permute-start.9)", 1.0 + 59e-3, 1.0 + 60e-3),
     10.0),
], ids=["synchronous", "unmatched-done"])
def test_exposed_transfer_edges(op, share):
    r = mesh_readings(extra=[tr.Event(*op)])
    assert metrics.read("exposed_transfer.dense", r) == pytest.approx(share)


def test_exposed_transfer_is_none_on_one_chip():
    assert metrics.read("exposed_transfer.dense",
                        mesh_readings(chips=1)) is None
    r = mesh_readings()
    r.trace = None
    assert metrics.read("exposed_transfer.dense", r) is None


def profiled_dispatches(log_dir, **metadata):
    """A CPU profile of two ``dbcsr.dispatch`` annotations in the
    window, with ``metadata`` on each, and the readings of it."""
    with jax.profiler.trace(str(log_dir)):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("dbcsr.dispatch",
                                                  **metadata):
                    pass
    from jax.profiler import ProfileData

    host = []
    for plane in ProfileData.from_file(str(tr.find(log_dir))).planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                host += [tr.Event(e.name, e.start_ns * 1e-9,
                                  e.end_ns * 1e-9) for e in line.events]
    (window,) = [(e.start, e.end) for e in host if e.name == tr.WINDOW]
    return Readings(steps=[Step(0, 0, 0, 0, 0, 0)] * 2, setup_s=0.0,
                    work=Work(1.0, 0.0), peaks=PEAKS,
                    trace=tr.Trace(window, [[]], host), log=lambda m: None)


def test_comm_bytes_is_none_without_the_metadata(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    r = profiled_dispatches(tmp_path)
    assert metrics.read("comm_bytes.dense", r) is None


def test_comm_bytes_reads_the_dispatch_metadata(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    r = profiled_dispatches(tmp_path, comm_bytes=495616, comm_steps=2)
    assert metrics.read("comm_bytes.dense", r) == 495616.0
