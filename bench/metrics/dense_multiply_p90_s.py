"""90th percentile of the window's per-call latencies on the densified
path: from when the call was due (in a closed loop, the call into the
library) to its product on the device."""
import numpy as np


def read(r, path):
    if not r.steps:
        return None
    return float(np.percentile([s.t_done - s.t_due for s in r.steps], 90))
