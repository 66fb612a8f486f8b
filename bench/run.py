#!/usr/bin/env python3
"""DBCSR chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the cell's
chips.  Set-up makes the operands from the seed on the device, warms the
timed path up with one untimed call, and then one closed-loop caller
multiplies for ``--seconds``.  Products sampled from the window are
compared with a float32 reference once it has closed.  The last line of
standard output is one JSON object: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics read from a
profiler trace of the window.  The compared numbers and their limits are
the last lines of standard error.

It exits with 2, and prints no result, where the program is missing,
where an ``artifacts/`` table could steer the planner or the kernel,
where JAX finds no TPU, or where it finds another number of chips than
the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# tables the planner (planner/calibrate.py) and the smm kernel
# (kernels/smm/autotune.py) read from the working directory when present
STEERING = ("artifacts/planner_calibration.json",
            "artifacts/smm_autotune.json", "artifacts/bench/*.json")


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def prepare():
    """Check the checkout, point the compile cache into it and make the
    benchmark and the program importable; the problem found, or None."""
    if not (ROOT / "src" / "repro").is_dir():
        return f"the program is not in this checkout ({ROOT / 'src'})"
    for pattern in STEERING:
        for base in sorted({Path.cwd(), ROOT}):
            found = sorted(base.glob(pattern))
            if found:
                return (f"{found[0]} exists: a table left by an earlier run "
                        f"would steer the planner or the kernel")
    # the persistent compile cache lives in the checkout, at a fixed path;
    # the program's enable_compile_cache() takes the directory given here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0] = str(ROOT)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(1, str(ROOT / "src"))
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    problem = prepare()
    if problem:
        return fail(problem)

    from bench import harness
    from repro.compile_cache import enable_compile_cache

    try:
        cell = harness.load_cell(args.workload)
        devices = harness.tpu_devices(cell.chips)
        peaks = harness.peaks_for(devices[0].device_kind)
    except harness.BenchError as e:
        return fail(str(e))
    harness.log(f"compile cache {enable_compile_cache()}")
    result = harness.run_cell(
        cell, seed=args.seed % 2 ** 64, seconds=args.seconds,
        traced=bool(args.trace), devices=devices, peaks=peaks,
        t_process=T_PROCESS)
    harness.print_checks(result["checks"], sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
