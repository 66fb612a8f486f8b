"""Share of the traced window in which a collective ran on a chip and no
other operation did, in %, the mean over the chips."""
import re

from bench import trace as tr

COLLECTIVE = re.compile(r"all-reduce|all-gather|collective-permute|"
                        r"reduce-scatter|all-to-all|send|recv")


def read(r, path):
    if r.trace is None or len(r.trace.devices) < 2:
        return None
    shares = []
    for d, ops in enumerate(r.trace.devices):
        coll = [(e.start, e.end) for e in ops if COLLECTIVE.search(e.name)]
        rest = [(e.start, e.end) for e in ops
                if not COLLECTIVE.search(e.name)]
        alone = tr.subtract(tr.clip(coll, r.trace.window), rest)
        shares.append(tr.length(alone) / r.trace.window_s)
    return 100.0 * sum(shares) / len(shares)
