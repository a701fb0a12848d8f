#!/usr/bin/env python3
"""The control of ``vitdet-b-mask``'s ``correct`` comparison, and its
readings: the ``control`` row of ``benchmark/README.md``, "A configuration".

    python3 benchmark/vitdet/control.py --workload vitdet-serve-closed --seeds 21 22 23

As ``benchmark/mask/control.py`` does for ``r101-fpn-mask``: for each seed
the configuration's weights and a few of the cell's request bodies (the
largest among them); the plain reference in float32 gives the dense
candidates and keeps the pyramid; the same reference computed in float8
(the nearest precision below the configuration's bfloat16: every matmul's
inputs, the attention's q, k, v and probabilities among them) is put in the
program's place — its whole pipeline down to the record list, then its own
mask branch over its own float8 pyramid at its own records' boxes, pasted
and cut at 0.5 — and held against the float32 reference with the comparison
a run uses.  It has to come out as not correct.  Not part of a benchmark
run; the chip run of record is in PERF.md, and
``tests/benchmark_checks/test_vitdet_run.py`` runs it at a size a test can
hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def with_masks(recs: list, dense_doc: dict, net: dict, reference) -> list:
    """The records with the mask branch of ``dense_doc``'s own precision
    pasted into the frame and cut at 0.5: what a server computing so would
    answer."""
    import numpy as np

    from benchmark.mask.control import encode_mask

    h, w = dense_doc["hw"]
    ref = reference.masks(dense_doc, np.asarray([r["bbox"] for r in recs]),
                          [r["cls"] for r in recs], net)
    out = []
    for rec, ((x0, y0), prob) in zip(recs, ref):
        bits = np.zeros((h, w), bool)
        bits[y0:y0 + prob.shape[0], x0:x0 + prob.shape[1]] = prob >= 0.5
        out.append(dict(rec, segmentation=encode_mask(bits)))
    return out


def control_numbers(config: dict, traffic: dict, seed: int, bodies: int = 8,
                    precision: str = "fp8") -> dict:
    """The comparison's numbers with the lower-precision reference in the
    program's place, on ``bodies`` bodies of the seed's pool + the largest."""
    import numpy as np

    from benchmark import harness, loadgen
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
        dense.append(mods["reference"].detect(flat, doc, net, "f32"))
        low = mods["reference"].detect(flat, doc, net, precision)
        recs = records(low["prob"], low["boxes"], net["num_classes"],
                       net["test_thresh"], net["test_nms"],
                       net["test_max_per_image"])
        sample.append({"doc": doc, "detections": with_masks(
            recs, low, net, mods["reference"])})
    return mods["compare"].compare(sample, dense, net)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--bodies", type=int, default=8)
    args = ap.parse_args(argv)
    from benchmark import harness

    spec = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    harness.require_chips(spec["cell"]["chips"])
    judge = harness.modules_of(spec["config"])["compare"].judge
    for seed in args.seeds:
        numbers = control_numbers(spec["config"], spec["traffic"], seed,
                                  bodies=args.bodies,
                                  precision=args.precision)
        ok, _ = judge(numbers, spec["config"]["correct"])
        print(json.dumps({"seed": seed, "precision": args.precision,
                          "correct": ok, "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
