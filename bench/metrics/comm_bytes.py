"""Bytes the program counts into the busiest chip per multiply: the mean
``comm_bytes`` of the window's ``dbcsr.dispatch`` spans, which the
schedule engine's host-static accounting attaches (the collective
phases and the resharding at the ``shard_map`` boundary, in the
operands' dtype).  None where no span carries it."""
from bench import spans


def read(r, path):
    if r.trace is None or not r.steps:
        return None
    found = [float(m["comm_bytes"]) for m in
             spans.metadata(r.trace.window, "dbcsr.dispatch")
             if "comm_bytes" in m]
    return sum(found) / len(found) if found else None
