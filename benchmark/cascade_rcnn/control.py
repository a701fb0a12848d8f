#!/usr/bin/env python3
"""The control of ``r101-fpn-cascade-mask``'s ``correct`` comparison, and its
readings: the ``control`` row of ``benchmark/README.md``, "A configuration".

    python3 benchmark/cascade_rcnn/control.py --workload cascade-serve-closed --seeds 21 22 23

``benchmark.mask.control``'s procedure, on this configuration's modules:
for each seed the configuration's
weights and a few of the cell's request bodies (the largest among them);
the plain reference (``reference.cmrcnn_fpn``) in float32 gives the dense
candidates and keeps the pyramid; the same reference computed in float8
(the nearest precision below the configuration's bfloat16) — its pyramid,
RPN and all three box stages — is put in the program's place down to the
record list, its own mask branch at its own records' boxes, and held
against the float32 reference with the comparison a run uses.  It has to
come out as not correct, by a limit that rounding moves (a box, score or
mask gap); the two numbers on the stages read alike for both, since the
control runs every stage.  With ``--precision f32 --order 1`` (or ``1 2
2``) the stand-in is the float32 reference that skips stages 2 and 3 (or
runs stage 2 twice): the readings that set the limits of those two.  Not part of a benchmark run; the chip run of
record is in PERF.md, and ``tests/benchmark_checks/test_cascade_rcnn_run.py``
runs it at a size a test can hold.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_numbers(config: dict, traffic: dict, seed: int, bodies: int = 8,
                    precision: str = "fp8", order=None) -> dict:
    """The comparison's numbers with the reference in ``precision`` put in
    the program's place, on ``bodies`` bodies of the seed's pool + the
    largest.  ``order``: the stage heads that stand-in runs (default all,
    in turn): ``(1,)`` answers stage 1's boxes b_2 and scores, ``(1, 2,
    2)`` runs stage 2 twice — what the two numbers on the stages are
    there to catch (PERF.md section 2 has their readings)."""
    import json

    import numpy as np

    from benchmark import harness, loadgen
    from benchmark.mask.control import with_masks
    from benchmark.reference import cmrcnn_fpn
    from benchmark.reference.frcnn_c4 import records

    mods = harness.modules_of(config)
    net = config["net"]
    flat = mods["weights"].make(net, seed)
    pool = loadgen.make_bodies(traffic["bodies"], seed)
    rng = np.random.default_rng([int(seed), 4])
    pick = [int(i) for i in rng.choice(len(pool), size=min(bodies, len(pool)),
                                       replace=False)]
    longest = max(range(len(pool)), key=lambda i: len(pool[i]))
    if longest not in pick:
        pick.append(longest)
    sample, dense = [], []
    for i in pick:
        doc = json.loads(pool[i])
        dense.append(cmrcnn_fpn.detect(flat, doc, net, "f32"))
        low = cmrcnn_fpn.detect(flat, doc, net, precision, order=order)
        recs = records(low["prob"], low["boxes"], net["num_classes"],
                       net["test_thresh"], net["test_nms"],
                       net["test_max_per_image"])
        sample.append({"doc": doc,
                       "detections": with_masks(recs, low, net)})
    return mods["compare"].compare(sample, dense, net)


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--bodies", type=int, default=8)
    ap.add_argument("--order", type=int, nargs="+", default=None,
                    help="the stage heads the stand-in runs (default all)")
    args = ap.parse_args(argv)
    from benchmark import harness

    spec = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    harness.require_chips(spec["cell"]["chips"])
    judge = harness.modules_of(spec["config"])["compare"].judge
    for seed in args.seeds:
        numbers = control_numbers(spec["config"], spec["traffic"], seed,
                                  bodies=args.bodies,
                                  precision=args.precision, order=args.order)
        ok, _ = judge(numbers, spec["config"]["correct"])
        print(json.dumps({"seed": seed, "precision": args.precision,
                          "order": args.order, "correct": ok,
                          "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
