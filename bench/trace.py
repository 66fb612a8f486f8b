"""From a profiler trace to intervals: the device operations of each chip
and the host's activity, on the trace's one clock.

The traced run wraps its whole measured window in a host annotation
named ``bench.window``; every reducer works inside that window.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float     # seconds on the trace's clock
    end: float
    category: str = ""   # a device op's HLO category ("convolution fusion")


@dataclasses.dataclass
class Trace:
    window: Interval
    devices: List[List[Event]]   # device operations, one list per chip
    host: List[Event]            # host events of the benchmark's thread

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _xplane_pb2():
    """The profiler's protocol buffer module, as the installed TensorFlow
    ships it, loaded from its file alone (not through TensorFlow)."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        raise ImportError("reading HLO categories from a trace needs "
                          "tensorflow's tsl/profiler/protobuf/xplane_pb2.py")
    path = (Path(spec.origin).parent / "tsl" / "profiler" / "protobuf"
            / "xplane_pb2.py")
    module_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def categories(path: Path) -> Dict[str, str]:
    """The HLO category of each device op, by the op's name, from the
    metadata the profiler records with it."""
    space = _xplane_pb2().XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        stat = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for st in meta.stats:
                if stat.get(st.metadata_id) == "hlo_category":
                    out[meta.name] = (st.str_value or stat.get(
                        st.ref_value, ""))
    return out


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    category = categories(path)
    host: List[Event] = []
    devices: Dict[int, List[Event]] = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                          for e in line.events]
                if any(e.name == WINDOW for e in events):
                    host += events
        elif DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(int(plane.name.rsplit(":", 1)[1]), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(e.name, e.start_ns * 1e-9,
                                  e.end_ns * 1e-9,
                                  category.get(e.name, ""))
                            for e in line.events]
    windows = [(e.start, e.end) for e in host if e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {WINDOW} annotations")
    if not devices:
        raise ValueError(f"{path}: no device plane with {OPS_LINE!r}")
    return Trace(windows[0], [devices[i] for i in sorted(devices)], host)


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The points of ``a`` that lie in no interval of ``b``."""
    out: List[Interval] = []
    b = union(b)
    for s, e in union(a):
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def busy(trace: Trace, device: int) -> List[Interval]:
    """When the chip ran any operation, inside the window."""
    return union(clip([(e.start, e.end) for e in trace.devices[device]],
                      trace.window))


def matching(trace: Trace, device: int, pattern: re.Pattern,
             category: re.Pattern) -> List[Event]:
    """The chip's ops in the window whose name matches ``pattern`` and
    whose HLO category matches ``category``."""
    lo, hi = trace.window
    return [e for e in trace.devices[device]
            if pattern.search(e.name) and category.search(e.category)
            and e.end > lo and e.start < hi]


def host_activity(trace: Trace, t: float) -> str:
    """The innermost host event that covers the instant ``t``."""
    covering = [e for e in trace.host if e.start <= t <= e.end]
    if not covering:
        return "(no host event)"
    return min(covering, key=lambda e: e.end - e.start).name
