"""XLA compile requests per multiply in the window, as ``jax.monitoring``
reports them, persistent-cache hits included."""
import numpy as np


def read(r, path):
    if not r.steps:
        return None
    return float(np.mean([s.compiles for s in r.steps]))
