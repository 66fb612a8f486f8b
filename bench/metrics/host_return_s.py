"""Mean host seconds from the call into the library to its return, before
the wait on the device: the API, the planner and, on the blocked path,
stack generation."""
import numpy as np


def read(r, path):
    if not r.steps:
        return None
    return float(np.mean([s.t_return - s.t_call for s in r.steps]))
