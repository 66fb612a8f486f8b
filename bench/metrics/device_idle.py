"""Share of the traced window in which no operation ran on a chip, in %,
the mean over the chips."""
from bench import trace as tr


def read(r, path):
    if r.trace is None:
        return None
    w = r.trace.window_s
    idle = [1.0 - tr.length(tr.busy(r.trace, d)) / w
            for d in range(len(r.trace.devices))]
    return 100.0 * sum(idle) / len(idle)
