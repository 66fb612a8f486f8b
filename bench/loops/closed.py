"""A closed loop with one caller: each step is formed as soon as the
product of the step before it is on the device, as a solver's inner loop
calls the library.  The window runs whole steps until ``seconds`` have
passed; the last one, which ends after that, counts whole.  A step that
raises ends the window and counts as failed."""
from __future__ import annotations

import traceback


def run(do, draws, seconds, now, log) -> int:
    while True:
        try:
            step = do(next(draws), None)
        except Exception:   # the window stops at a failed step, reported
            log(traceback.format_exc())
            return 1
        if step.t_done >= seconds:
            return 0
