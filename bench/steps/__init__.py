"""What one step of a traffic mix does, one module per kind, found by the
name a mix gives under ``step``.  Each module has

    build(config: dict, operands: Operands, mesh, chips: int) -> Stepper

and its stepper holds the program's state for the cell and knows how to
drive one step and how to check its product:

    work             Work of one step on the busiest chip (bench/work.py),
                     counted from the operands, never read from the program
    form(s)          the step's inputs at the mix's draw ``s``
    call(x)          the library call on them; returns the product
    wait(c)          blocks until the product ``c`` is on the device
    describe(c)      the program's executed choice, as one line
    release()        drops the program's state before the reference runs
    compare(s, c)    the compared numbers of ``c``, the product of draw ``s``
    control(s)       the same numbers of the control's product at ``s``
    invariant(ss)    ``(holds, message)``: what the work count assumes of
                     every draw ``ss`` the window used

The harness times ``form``, ``call`` and ``wait`` around each other, and
everything else happens outside the measured window.
"""
from __future__ import annotations

import importlib


def load(kind: str):
    return importlib.import_module(f"bench.steps.{kind}")
