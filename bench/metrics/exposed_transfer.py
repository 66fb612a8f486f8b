"""Share of the traced window in which a chip has a collective transfer
in flight and runs no other operation, in %, the mean over the chips:
the communication that nothing hides.

An asynchronous collective is in flight from the beginning of its
``-start`` op to the end of the ``-done`` op that takes that start as
its operand; a synchronous one is its own op.  Ops are told apart by
their own names, the text before `` = `` in a trace's HLO op name,
never by the operands they take: a dot that consumes a ``-done`` is no
collective.  A ``-done`` whose start the trace does not hold is left
out.  None on one chip."""
from __future__ import annotations

import re
from typing import List, Set, Tuple

from bench import trace as tr

COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|send|recv)")
OWN_NAME = re.compile(r"^%?([\w.\-]+)")
OPERAND = re.compile(r"%([\w.\-]+)")


def own_name(op: str) -> str:
    """An op's own name from its HLO text on the trace
    (``%collective-permute-done.1 = bf16[...] ...`` gives
    ``collective-permute-done.1``)."""
    found = OWN_NAME.match(op)
    return found.group(1) if found else op


def transfers(ops) -> Tuple[List[tr.Interval], List[tr.Interval],
                            Set[str]]:
    """A chip's collective transfers as intervals, its other ops'
    intervals, and the names of the transfers matched."""
    started = {}
    moving, rest, names = [], [], set()
    for e in sorted(ops, key=lambda e: e.start):
        name = own_name(e.name)
        if not COLLECTIVE.match(name):
            rest.append((e.start, e.end))
        elif "-start" in name:
            started[name] = e
        elif "-done" in name:
            operands = OPERAND.findall(e.name.split("=", 1)[-1])
            key = next((o for o in operands if o in started), None)
            if key is not None:
                moving.append((started.pop(key).start, e.end))
                names.add(f"{key} .. {name}")
        else:
            moving.append((e.start, e.end))
            names.add(name)
    return moving, rest, names


def read(r, path):
    if r.trace is None or len(r.trace.devices) < 2:
        return None
    shares, names = [], set()
    for ops in r.trace.devices:
        moving, rest, matched = transfers(ops)
        names |= matched
        alone = tr.subtract(tr.clip(moving, r.trace.window), rest)
        shares.append(tr.length(alone) / r.trace.window_s)
    r.log(f"  exposed_transfer matched {len(names)} transfers: "
          f"{sorted(names)[:20]}")
    return 100.0 * sum(shares) / len(shares)
