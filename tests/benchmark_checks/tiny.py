"""The tiny size the CPU checks run at: the cell's own spec with every size
cut (ResNet-50, a 96x128 bucket, 300 -> 30 proposals, batch 2).  Tests may
cut sizes; the benchmark never does."""

import copy

from benchmark import harness

# set from CPU runs at this size: the program reads box_gap 0.002 and
# score_gap 0.004 (seed 2**31 + 3), the float8 control 0.050-0.091 and
# 0.040-0.043 (seeds 1-3)
LIMITS = {"records": 50, "box_gap": 0.009, "score_gap": 0.018,
          "far_share": 0.05, "order_faults": 0, "low_scores": 0,
          "nms_faults": 0}


def canned_window(records: list, extra: dict | None = None) -> dict:
    """What the generator reports of a window, canned: three requests, all
    answered, one of them sampled — its body, and its whole response
    document (``records`` under ``detections`` + whatever ``extra`` adds)."""
    response = dict({"detections": records, "queue_wait_ms": 1.0},
                    **(extra or {}))
    return {"event": "result", "attempted": 3, "failed": 0,
            "status": {"200": 3}, "serve_imgs_per_s": 1.0,
            "sample": [{"body": 0, "doc": {"shape": [2, 2, 3], "data": ""},
                        "response": response, "detections": records}]}


def tiny_spec(workload: str = "c4-serve-open",
              root: str = harness.ROOT) -> dict:
    spec = copy.deepcopy(harness.load_cell(workload, root=root))
    c = spec["config"]
    c["network"] = "resnet50"
    c["cfg"] = ["tpu__SCALES=((96,128),)", "TEST__RPN_PRE_NMS_TOP_N=300",
                "TEST__RPN_POST_NMS_TOP_N=30"]
    c["serve_flags"] = ["--synthetic", "--serve-batch", "2",
                        "--max-delay-ms", "10", "--max-queue", "64"]
    c["batch_per_chip"] = 2
    c["net"].update(depth="resnet50", scale=[96, 128], test_pre_nms=300,
                    test_post_nms=30)
    c["correct"] = dict(LIMITS)
    t = spec["traffic"]
    t["rate"] = 4.0
    t["bodies"] = {"pool": 8, "short": [60, 80], "long": [90, 120],
                   "portrait_every": 4}
    t["warm_per_orientation"] = 2
    t["sample"] = 4
    return spec
