"""The one traffic generator.  A mix is a data file,
``bench/traffic/<mix>.json``, of parameters that this module reads:

    loop        how steps are offered in the window: the module
                ``bench/loops/<loop>.py`` ("closed": one caller that waits
                for each product before it forms the next operand)
    step        what one step does: the module ``bench/steps/<step>.py``
                ("scaled_multiply": one multiply of a fresh operand)
    scale_low, scale_high
                each step's draw is a factor ``s_t`` drawn uniformly from
                this range, by which the step scales its operand
    min_change  consecutive factors differ by at least this much, so a
                step never gets the product of the step before it right
                by chance

The draws come from the run's seed alone; every seed asks for the same
work, at other values.  A mix that needs another kind of loop or step
adds its module beside the others and names it here.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterator

import numpy as np

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent


def load(mix: str) -> dict:
    params = json.loads((HERE / f"{mix}.json").read_text())
    for key, where in (("loop", "loops"), ("step", "steps")):
        if not (BENCH / where / f"{params.get(key)}.py").is_file():
            raise ValueError(f"traffic {mix}: no bench/{where}/"
                             f"{params.get(key)}.py for its {key}")
    if not 0 < params["scale_low"] < params["scale_high"]:
        raise ValueError(f"traffic {mix}: bad scale range")
    if not 0 <= 2 * params["min_change"] < (params["scale_high"]
                                             - params["scale_low"]):
        raise ValueError(f"traffic {mix}: min_change leaves no room")
    return params


def scales(params: dict, seed: int) -> Iterator[float]:
    """The factors ``s_1, s_2, ...`` of one run."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    last = None
    while True:
        s = float(rng.uniform(params["scale_low"], params["scale_high"]))
        if last is None or abs(s - last) >= params["min_change"]:
            last = s
            yield s
