#!/usr/bin/env python3
"""One cell, once: ``python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.  Sets up, warms the cell's own shapes,
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints the result as the last line of standard output.
With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones.  Finds no TPU: builds nothing, prints no
result, exits non-zero.

Where this checkout's compile cache does not hold the cell's programs yet,
they are built first in a child (this command with ``--precompile``: the
driver's set-up, no window, no result) that exits before this process
touches jax; ``setup_s`` counts it."""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--precompile", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from benchmark import harness

    spec = harness.load_cell(args.workload)
    if not args.precompile:
        harness.ensure_programs_cached(spec, args.seed)
    driver = importlib.import_module(
        "benchmark.drivers." + spec["traffic"]["kind"])
    harness.setup_compile_cache()
    device = harness.require_chips(spec["cell"]["chips"])
    if args.precompile:
        if hasattr(driver, "precompile"):
            driver.precompile(spec, args.seed)
        harness.write_programs_marker(spec)
        return 0
    line, compared = driver.run(spec, args.seed, args.seconds,
                                bool(args.trace), device, T_START)
    sys.stdout.flush()
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
