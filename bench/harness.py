"""One run of one cell: set-up, a timed window, the comparison with the
reference, and the result line.

Everything a cell needs is found by name.  ``BENCHMARK.json`` names the
cell, its configuration, its traffic mix and its metrics;
``bench/workloads/<cell>.json`` adds the mesh and the limits of the
comparison; the configuration's file its shapes, dtype, operand
generator (``bench/operands/<operand>.py``) and the path it pins; the
mix's file its loop (``bench/loops/<loop>.py``), its step
(``bench/steps/<step>.py``) and its draws; and each metric is read by
``bench/metrics/<family>.py``.  This module only sets up, times the
steps, runs the comparison and reads the metrics.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import shutil
import time
from pathlib import Path
from typing import Callable, List, Tuple

import numpy as np

from bench import loops, metrics, operands, steps, traffic
from bench import trace as tr
from bench.metrics import Readings, Step

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
TRACE_DIR = HERE / "out" / "trace"
# timed products kept for the comparison, a uniform sample drawn from the
# seed (reservoir sampling), since a window's length in steps is not known
# before it closes
SAMPLES = 2


class BenchError(RuntimeError):
    """The run cannot measure what the cell asks for; no result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def clock() -> float:
    return time.perf_counter()


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    mesh: Tuple[int, int]
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Tuple[str, str]]   # (name, unit)
    per_layer: List[Tuple[str, str]]


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    entry = cells[name]
    spec = _json(root / "bench" / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise BenchError(f"{name}: {key} is {spec[key]!r} in its file "
                             f"and {entry[key]!r} in BENCHMARK.json")
    config_file = {c["name"]: c["file"] for c in bench["configs"]}
    e2e = [(m["name"], m["unit"]) for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [(m["name"], m["unit"]) for m in bench["per_layer"]
                 if name in m["workloads"]]
    return Cell(name=name, chips=entry["chips"], mesh=tuple(spec["mesh"]),
                config=_json(root / config_file[entry["config"]]),
                traffic=traffic.load(entry["traffic"]),
                limits=spec["limits"], end_to_end=e2e, per_layer=per_layer)


def peaks_for(kind: str) -> dict:
    table = _json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (has {sorted(table)})")
    return table[kind]


def tpu_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform "
                         f"{devices[0].platform!r} x{len(devices)}")
    if len(devices) != chips:
        raise BenchError(f"the cell asks for {chips} chip(s); JAX found "
                         f"{len(devices)}")
    return devices


# ---------------------------------------------------------------------------
# compile requests
# ---------------------------------------------------------------------------


class CompileCounter:
    """XLA compile requests, persistent-cache hits included, and the hits
    apart, as ``jax.monitoring`` reports them."""

    def __init__(self):
        import jax

        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@functools.lru_cache(maxsize=None)
def compile_counter() -> CompileCounter:
    """The process's one counter: listeners cannot be taken off again."""
    return CompileCounter()


# ---------------------------------------------------------------------------
# the timed path
# ---------------------------------------------------------------------------


def build(cell: Cell, seed: int, devices: list):
    """The cell's operands on its mesh, made from ``seed``, and the
    stepper of its traffic's step kind that holds them."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices).reshape(cell.mesh), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sharding = NamedSharding(mesh, P("data", "model"))
    ops = operands.load(cell.config["operand"]).make(cell.config, seed,
                                                     sharding)
    return steps.load(cell.traffic["step"]).build(cell.config, ops, mesh,
                                                  cell.chips)


def timed_step(stepper, s: float, t0: float,
               t_due=None) -> Tuple[Step, object]:
    """One step at draw ``s``: ``(Step, product)``, times since ``t0``."""
    import jax

    counter = compile_counter()
    n0, h0 = counter.n, counter.hits
    t_start = clock()
    with jax.profiler.TraceAnnotation("bench.form"):
        x = stepper.form(s)
    t_call = clock()
    with jax.profiler.TraceAnnotation("bench.call"):
        c = stepper.call(x)
    t_return = clock()
    with jax.profiler.TraceAnnotation("bench.wait"):
        stepper.wait(c)
    t_done = clock()
    return Step(t_start - t0, t_call - t0, t_return - t0, t_done - t0,
                counter.n - n0, counter.hits - h0,
                t_call - t0 if t_due is None else t_due), c


def keep_sample(kept: list, t: int, item, rng) -> None:
    """Reservoir sampling: after step ``t`` (0-based) ``kept`` holds a
    uniform sample of ``SAMPLES`` of the steps so far."""
    if len(kept) < SAMPLES:
        kept.append(item)
        return
    j = int(rng.integers(0, t + 1))
    if j < SAMPLES:
        kept[j] = item


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
             devices: list, peaks: dict, t_process: float,
             log: Callable[[str], None] = log) -> dict:
    """Set up, warm up, measure for ``seconds``, compare, and return the
    result object."""
    import jax

    compile_counter()
    stepper = build(cell, seed, devices)
    draws = traffic.scales(cell.traffic, seed)
    rng = np.random.default_rng([seed, 0x5A3B])
    log(f"cell {cell.name}: {cell.config['name']} on "
        f"{'x'.join(map(str, cell.mesh))} {devices[0].device_kind}, "
        f"{cell.traffic['loop']} loop of {cell.traffic['step']}, "
        f"{cell.config.get('multiply')}; work per step per chip "
        f"{stepper.work}, {stepper.work.bound(peaks)}-bound")

    warm, c = timed_step(stepper, next(draws), clock())
    log(f"program's executed choice (warm-up call): {stepper.describe(c)}; "
        f"warm-up {warm.t_done - warm.t_start:.4f} s, {warm.compiles} "
        f"compile requests ({warm.cache_hits} persistent-cache hits)")
    del c
    setup_s = clock() - t_process

    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # host spans, not every call
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    window: List[Step] = []
    used: List[float] = []
    kept: list = []
    t0 = clock()

    def do(s: float, t_due=None) -> Step:
        step, c = timed_step(stepper, s, t0, t_due)
        window.append(step)
        used.append(s)
        keep_sample(kept, len(window) - 1, (len(window) - 1, s, c), rng)
        return step

    with jax.profiler.TraceAnnotation(tr.WINDOW):
        failed = loops.load(cell.traffic["loop"]).run(
            do, draws, seconds, lambda: clock() - t0, log)
    if traced:
        jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    fresh = sum(s.compiles - s.cache_hits for s in window)
    log(f"window: {len(window)} steps in "
        f"{window[-1].t_done if window else 0.0:.4f} s, "
        f"{sum(s.compiles for s in window)} compile requests "
        f"({fresh} not served by the persistent cache); failed {failed}")

    # the program's state goes before the reference runs
    stepper.release()
    checks = {name: {"value": 0.0, "limit": limit}
              for name, limit in cell.limits.items()}
    for t, s, c in sorted(kept, key=lambda k: k[0]):
        got = stepper.compare(s, c)
        log(f"step {t} (draw {s:.6f}): {got}")
        for name in checks:
            checks[name]["value"] = max(checks[name]["value"], got[name])
    kept.clear()
    invariant, message = stepper.invariant(used)
    log(message)
    correct = (failed == 0 and bool(window) and invariant and all(
        c["value"] <= c["limit"] for c in checks.values()))

    readings = Readings(steps=window, setup_s=setup_s, work=stepper.work,
                        peaks=peaks, log=log)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": len(window) + failed,
              "failed": failed}
    breakdown = None
    if traced:
        readings.trace = tr.load(tr.find(TRACE_DIR))
        wanted = cell.per_layer
        device["busy_s"] = sum(
            tr.length(tr.busy(readings.trace, d))
            for d in range(len(devices))) / len(devices)
        device["window_s"] = readings.trace.window_s
        breakdown = breakdown_of(readings.trace)
    else:
        wanted = cell.end_to_end
    values = {}
    for name, unit in wanted:
        value = metrics.read(name, readings)
        if value is not None:
            values[name] = {"value": value, "unit": unit}
    result.update(metrics=values, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def breakdown_of(trace: tr.Trace, top: int = 10) -> dict:
    """The device operations that took most time (by their HLO text, cut
    to 160 characters), and the idle time by what the host was doing in
    it, each in seconds per chip."""
    chips = len(trace.devices)
    op_s: dict = {}
    idle_s: dict = {}
    for d in range(chips):
        lo, hi = trace.window
        for e in trace.devices[d]:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                name = e.name[:160]
                op_s[name] = op_s.get(name, 0.0) + (t - s) / chips
        for s, t in tr.subtract([trace.window], tr.busy(trace, d)):
            label = tr.host_activity(trace, (s + t) / 2)
            idle_s[label] = idle_s.get(label, 0.0) + (t - s) / chips

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return {"device_ops": ranked(op_s), "idle_gaps": ranked(idle_s)}


def print_checks(checks: dict, stream) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=stream, flush=True)
