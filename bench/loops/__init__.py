"""How a traffic mix offers its steps in the measured window, one module
per kind, found by the name a mix gives under ``loop``.  Each module has

    run(do, draws, seconds, now, log) -> int

which offers steps until the window of ``seconds`` has passed and
returns how many failed.  ``do(draw, t_due)`` runs one whole step (form,
call, wait), records it and returns its ``Step``, whose times count from
the window's opening; ``t_due`` is when the step was due, which latency
counts from (``None``: when it was formed).  ``draws`` yields the mix's
draws in order, ``now()`` is the window's clock, and ``log`` takes a
line for standard output.
"""
from __future__ import annotations

import importlib


def load(kind: str):
    return importlib.import_module(f"bench.loops.{kind}")
