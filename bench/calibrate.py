#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip and at the
cell's own size: the compared numbers of the program's timed path on a
dozen seeds or more, and of the control (``reference.control``, the
reference at the precision below) on three or more.  All seeds run in
one process, so that set-up and compiles are paid once.

    python3 bench/calibrate.py --workload <cell> --seed <n> --seeds 12 \\
        --control-seeds 3

Seeds are ``seed, seed + 1, ...``; each takes as many timed-path products
as a run compares, through the run's own set-up and timed step, and
compares them as a run does, once the program's state is released.  The benchmark's own runs never run this.  The last
line of standard output is one JSON object with every reading.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)

from bench import run as entry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    problem = entry.prepare()
    if problem:
        return entry.fail(problem)

    from bench import harness, traffic
    from repro.compile_cache import enable_compile_cache

    try:
        cell = harness.load_cell(args.workload)
        devices = harness.tpu_devices(cell.chips)
    except harness.BenchError as e:
        return entry.fail(str(e))
    enable_compile_cache()
    program, control = [], []
    for i in range(args.seeds):
        seed = (args.seed + i) % 2 ** 64
        stepper = harness.build(cell, seed, devices)
        draws = traffic.scales(cell.traffic, seed)
        first = None
        products = []
        for _ in range(harness.SAMPLES):
            s = next(draws)
            first = s if first is None else first
            step, c = harness.timed_step(stepper, s, harness.clock())
            products.append((s, c, step.t_done - step.t_start))
        stepper.release()
        for s, c, seconds in products:
            got = stepper.compare(s, c)
            program.append(dict(got, seed=seed, draw=s, seconds=seconds))
            harness.log(f"program seed {seed}: {program[-1]}")
        products.clear()
        c = None
        if i < args.control_seeds:
            control.append(dict(stepper.control(first), seed=seed,
                                draw=first))
            harness.log(f"control seed {seed}: {control[-1]}")
        del stepper
    names = [k for k in program[0] if k not in ("seed", "draw", "seconds")]
    summary = {name: {"program_max": max(p[name] for p in program),
                      "control_min": min((c[name] for c in control
                                          if name in c), default=None)}
               for name in names}
    for name, v in summary.items():
        harness.log(f"{name}: program max {v['program_max']!r}, control min "
                    f"{v['control_min']!r}")
    print(json.dumps({"workload": cell.name, "summary": summary,
                      "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
