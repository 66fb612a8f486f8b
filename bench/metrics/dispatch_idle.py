"""Share of the traced window, in %, in which a chip runs nothing while
the host is inside the program's ``dbcsr.dispatch``, the mean over the
chips: the device's idle time put down to the dispatch phase."""
from bench import spans
from bench import trace as tr


def read(r, path):
    if r.trace is None:
        return None
    inside = spans.intervals(r, "dbcsr.dispatch")
    if not inside:
        return None
    idle = [tr.length(tr.subtract(inside, tr.busy(r.trace, d)))
            for d in range(len(r.trace.devices))]
    return 100.0 * sum(idle) / len(idle) / r.trace.window_s
