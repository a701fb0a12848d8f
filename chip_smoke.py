#!/usr/bin/env python
"""Proof that the main path starts, compiles and answers on the TPU chip.

    python chip_smoke.py              # one chip: train, serve, kernels
    python chip_smoke.py --chips 4    # four chips: data-parallel train only

Config of record: ``--network resnet101`` (R101-C4 Faster R-CNN, the
BASELINE.json headline) at the 608x1024 bucket with the full proposal
contracts (12000->2000 train, 6000->300 test, MAX_GT 100) — nothing shrunk
beyond the bucket and what ``--synthetic`` itself sets.  Weights and data
are generated from fixed seeds; no network, no dataset.

One process per chip: this parent never initialises a jax back end.  Each
phase runs in a child of its own (``run_phase``), one after another, and
the device identity in the last line comes from the children's lines.  The
children share the program's compile cache (``JAX_COMPILATION_CACHE_DIR``,
else ``.jax_cache`` in the checkout), so a second run loads what the first
compiled.

* **train** — ``train_end2end.py --synthetic --num-steps N --devices 1``
  through its own ``parse_args``/``train_net``: every loss the Speedometer
  fetched is finite, the returned state counts N steps, and the step
  program holds the Pallas NMS kernel.
* **serve** — ``serve.py --synthetic --serve-batch 4`` through its own
  ``parse_args``/``main`` on a Unix socket, with a client thread that
  waits for ``/readyz``, posts ``encode_image_payload`` requests (both
  orientations, enough to fill a batch each) and reads ``/metrics``:
  all 200, every response a well-formed record list,
  ``recompiles == warmup_programs``, the predict program holds the kernel,
  and ``libmxr_native.so`` — rebuilt from the committed source by the
  parent just before — is the NMS that answered.
* **kernels** — the public ``nms_pallas`` against ``nms_padded`` on the
  chip at 12000->2000 and 6000->300, a few seeds: equal, and lowered with
  the ``tpu_custom_call`` inside (a run that took the oracle branch fails).
* **dp** (``--chips 4`` only) — the same train steps with ``--devices 4
  --batch_images 4`` against ``--devices 1 --batch_images 4`` on the same
  seed: per-step losses within ``DP_LOSS_BAND``, the step program
  partitioned four ways over the batch, and a replica of every parameter
  on each of the four devices.

Every phase prints one JSON line.  A phase that finds no TPU reports that
and builds nothing; nothing here falls back to the CPU.  The LAST line of
standard output is ``{"ok": ..., "device": {"platform", "kind",
"count"}}`` and the exit code is 0 only when every phase passed.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import logging
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))

NETWORK = "resnet101"
BUCKET_CFG = ("tpu__SCALES=((608,1024),)",)
TRAIN_STEPS = 8          # >= 5; every step's loss is fetched (--frequent 1)
SYNTHETIC_IMAGES = 16    # x2 with flips: covers 8 steps at a global batch of 4
SERVE_BATCH = 4
SERVE_REQUESTS = 8       # 4 landscape + 4 portrait: one full batch each
NMS_SHAPES = ((12000, 2000), (6000, 300))   # TRAIN and TEST contracts
NMS_SEEDS = 3
# 4-chip vs 1-chip per-step loss agreement, relative.  dryrun_multichip's
# 1e-3 (__graft_entry__.py) holds for ONE step between two equally
# partitioned programs; here a 4-way bf16 program meets a 1-device one,
# whose fusions round differently, and proposal/sampling flips follow.
# Measured on 4 x v5e (PR 21, CHANGES.md): RPN terms agree to 1e-4, the
# RCNN terms to 1-2 %; max deviation 1.93e-2 over 8 steps, not growing.
# The band is 2.6x that, and well under the 5-18 % by which the loss
# itself moves from one step to the next.
DP_LOSS_BAND = 5e-2
# the driver allows 1200 s, compilation included
BUDGET_S = 1100.0


def device_doc() -> dict:
    """The device as jax reports it — the one place a phase asks."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _phase(name: str):
    """Wrap a phase body: ask for the device first, build nothing unless
    it is a TPU, fold the body's checks into one printed JSON line."""
    def deco(body):
        @functools.wraps(body)
        def run(**kw) -> dict:
            t0 = time.perf_counter()
            device = device_doc()
            checks = {"platform_is_tpu": device["platform"] == "tpu"}
            extra = {}
            if checks["platform_is_tpu"]:
                body_checks, extra = body(device, **kw)
                checks.update(body_checks)
            doc = {"phase": name, "ok": all(checks.values()),
                   "device": device, "checks": checks, **extra,
                   "seconds": round(time.perf_counter() - t0, 3)}
            print(json.dumps(doc), flush=True)
            return doc
        return run
    return deco


def _cfg_flags(cfg) -> list:
    return [a for item in cfg for a in ("--cfg", item)]


@contextlib.contextmanager
def _lowered_programs():
    """Have jax write every module it lowers inside the block to a temp
    dir (its own ``jax_dump_ir_to``; lowering happens on a persistent-cache
    hit too) — how a phase reads the program a driver built without
    reaching into the driver."""
    import jax

    prev = jax.config.read("jax_dump_ir_to")
    chatter = logging.getLogger("jax._src.compiler")   # one line per module
    level = chatter.level
    with tempfile.TemporaryDirectory(prefix="mxr_ir_") as d:
        jax.config.update("jax_dump_ir_to", d)
        chatter.setLevel(logging.WARNING)
        try:
            yield d
        finally:
            chatter.setLevel(level)
            jax.config.update("jax_dump_ir_to", prev)


def _main_program(ir_dir: str) -> dict:
    """The largest lowered module — the train step in a train process, a
    predict program in a serve process — and what the checks read off it."""
    paths = [os.path.join(ir_dir, f) for f in os.listdir(ir_dir)]
    path = max(paths, key=os.path.getsize)
    with open(path) as f:
        text = f.read()
    parts = re.search(r"mhlo\.num_partitions = (\d+)", text)
    data = re.search(r'sdy\.mesh @mesh = <\["data"=(\d+)', text)
    return {"module": re.sub(r"^jax_ir\d+_|_compile\.mlir$", "",
                             os.path.basename(path)),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "partitions": int(parts.group(1)) if parts else 1,
            # jax 0.9.0 lowers NamedShardings to the Shardy dialect: the
            # mesh's data axis, and how many values are split on it along
            # their leading (batch) dimension
            "data_axis": int(data.group(1)) if data else 1,
            "split_on_data": len(re.findall(
                r'sdy\.sharding<@mesh, \[\{"data"\}', text))}


class _LossLog(logging.Handler):
    """What the trainer's Speedometer reports: the running mean of
    ``total_loss`` after each fetched step, with the record's wall time."""

    def __init__(self):
        super().__init__()
        self.means = []   # (record.created, mean) per Speedometer line

    def emit(self, record):
        msg = record.getMessage()
        m = re.search(r"total_loss=(\S+)", msg)
        if m and "Batch [" in msg:
            self.means.append((record.created, float(m.group(1))))


def _train(cfg, network: str, steps: int, devices: int,
           batch_images: int) -> dict:
    """``train_end2end.py --synthetic --num-steps N`` as the CLI runs it.

    With ``--frequent 1`` the trainer fetches every step's metrics and the
    Speedometer logs their running mean from the second step on, so the
    per-step losses are the differences of k x mean_k (the first entry is
    the mean of steps 1 and 2, which share the first line)."""
    import jax
    import train_end2end
    from mx_rcnn_tpu.logger import logger

    argv = ["--network", network, "--synthetic",
            "--synthetic_images", str(SYNTHETIC_IMAGES),
            "--num-steps", str(steps), "--end_epoch", "1",
            "--frequent", "1", "--devices", str(devices),
            "--batch_images", str(batch_images),
            "--prefix", "", *_cfg_flags(cfg)]
    log = _LossLog()
    logger.addHandler(log)
    t0 = time.time()
    try:
        with _lowered_programs() as ir_dir:
            state = train_end2end.train_net(train_end2end.parse_args(argv))
            jax.block_until_ready(state)
            program = _main_program(ir_dir)
    finally:
        logger.removeHandler(log)
    t1 = time.time()
    sums = [(k + 2) * m for k, (_, m) in enumerate(log.means)]
    losses = ([sums[0] / 2] + [b - a for a, b in zip(sums, sums[1:])]
              if sums else [])
    first = log.means[0][0] if log.means else t1
    # devices that hold a whole copy of EVERY parameter of the returned
    # state (addressable_shards — not everything on device 0)
    replicas = min(
        len({s.device for s in leaf.addressable_shards
             if s.data.shape == leaf.shape})
        for leaf in jax.tree.leaves(state.params))
    return {"losses": losses, "program": program, "replicas": replicas,
            "steps_run": int(jax.device_get(state.step)),
            "setup_s": round(first - t0, 3),
            "steady_s": round(t1 - first, 3)}


def _train_checks(run: dict, steps: int) -> dict:
    return {"steps_counted": run["steps_run"] == steps,
            "every_step_fetched": len(run["losses"]) == steps - 1,
            "losses_finite": all(math.isfinite(v) for v in run["losses"]),
            "kernel_in_program": run["program"]["tpu_custom_calls"] > 0}


def _train_extra(run: dict) -> dict:
    return {"losses": [round(v, 5) for v in run["losses"]],
            "program": run["program"], "setup_s": run["setup_s"],
            "steady_s": run["steady_s"]}


@_phase("train")
def phase_train(device, cfg=BUCKET_CFG, network=NETWORK, steps=TRAIN_STEPS):
    run = _train(cfg, network, steps, devices=1, batch_images=1)
    return _train_checks(run, steps), _train_extra(run)


@_phase("dp")
def phase_dp(device, cfg=BUCKET_CFG, network=NETWORK, steps=TRAIN_STEPS,
             band=DP_LOSS_BAND):
    """Data-parallel training over four devices against the one-device run
    of the same global batch and seed.  Losses that agree over every step
    cannot come from four chips that each trained alone, so agreement
    also shows the gradients were reduced."""
    if device["count"] < 4:
        return {"four_devices": False}, {}
    multi = _train(cfg, network, steps, devices=4, batch_images=4)
    single = _train(cfg, network, steps, devices=1, batch_images=4)
    dev = [abs(a - b) / max(1.0, abs(b))
           for a, b in zip(multi["losses"], single["losses"])]
    checks = {
        "four_devices": True,
        **{f"dp4_{k}": v for k, v in _train_checks(multi, steps).items()},
        **{f"dp1_{k}": v for k, v in _train_checks(single, steps).items()},
        "step_partitioned_4": multi["program"]["partitions"] == 4,
        "batch_split_4": (multi["program"]["data_axis"] == 4
                          and multi["program"]["split_on_data"] > 0),
        "params_replicated_on_4": multi["replicas"] == 4,
        "losses_agree": len(dev) == steps - 1 and max(dev) <= band,
    }
    return checks, {"dp4": _train_extra(multi), "dp1": _train_extra(single),
                    "loss_band": band,
                    "loss_rel_dev": [float(f"{v:.3g}") for v in dev]}


def _well_formed(records, num_classes: int) -> bool:
    return isinstance(records, list) and all(
        isinstance(r, dict)
        and isinstance(r.get("cls"), int) and 0 < r["cls"] < num_classes
        and isinstance(r.get("score"), float) and 0.0 <= r["score"] <= 1.0
        and isinstance(r.get("bbox"), list) and len(r["bbox"]) == 4
        and all(isinstance(c, float) and math.isfinite(c)
                for c in r["bbox"])
        for r in records)


def _serve_client(sock: str, scale, n: int, stop: threading.Event) -> dict:
    """The client half, on a thread of the serving process (so nothing
    else can claim the chip): wait for readiness, fire ``n`` concurrent
    /predict posts — alternating orientation, so ``n / 2`` land in each
    bucket — then read /metrics."""
    import numpy as np

    from mx_rcnn_tpu.serve import encode_image_payload, unix_http_request

    t0 = time.perf_counter()
    while True:
        try:
            status, _ = unix_http_request(sock, "GET", "/readyz", timeout=5)
        except OSError:
            status = None   # not bound yet
        if status == 200:
            break
        if stop.is_set() or time.perf_counter() - t0 > BUDGET_S:
            raise RuntimeError("server never became ready")
        time.sleep(0.5)
    t_ready = time.perf_counter()
    rng = np.random.RandomState(0)
    short, long_ = scale
    docs = []
    for i in range(n):
        h, w = (short - 8, long_ - 24) if i % 2 == 0 else (long_ - 24,
                                                          short - 8)
        docs.append(encode_image_payload(
            rng.randint(0, 256, (h, w, 3), dtype=np.uint8)))
    with ThreadPoolExecutor(n) as pool:
        replies = list(pool.map(
            lambda d: unix_http_request(sock, "POST", "/predict", d,
                                        timeout=600), docs))
    t_done = time.perf_counter()
    _, metrics = unix_http_request(sock, "GET", "/metrics", timeout=30)
    return {"replies": replies, "metrics": metrics,
            "setup_s": round(t_ready - t0, 3),
            "steady_s": round(t_done - t_ready, 3)}


@_phase("serve")
def phase_serve(device, cfg=BUCKET_CFG, network=NETWORK,
                requests=SERVE_REQUESTS):
    import serve
    from mx_rcnn_tpu import native
    from mx_rcnn_tpu.tools.common import config_from_args

    box, stop = {}, threading.Event()

    def client(sock, scale):
        try:
            box["out"] = _serve_client(sock, scale, requests, stop)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e
        finally:
            if not stop.is_set():   # ask serve.main for its graceful drain
                os.kill(os.getpid(), signal.SIGTERM)

    handlers = {s: signal.getsignal(s) for s in (signal.SIGTERM,
                                                 signal.SIGINT)}
    with tempfile.TemporaryDirectory(prefix="mxr_") as tmp, \
            _lowered_programs() as ir_dir:
        sock = os.path.join(tmp, "s.sock")
        args = serve.parse_args(
            ["--network", network, "--synthetic", "--unix-socket", sock,
             "--serve-batch", str(SERVE_BATCH), *_cfg_flags(cfg)])
        scfg = config_from_args(args, train=False)
        t = threading.Thread(target=client, args=(sock, scfg.tpu.SCALES[0]),
                             name="smoke-client", daemon=True)
        t.start()
        try:
            serve.main(args)   # returns once the client's SIGTERM drained it
        finally:
            stop.set()
            t.join(timeout=60)
            for s, h in handlers.items():
                signal.signal(s, h)
        program = _main_program(ir_dir)
    if "error" in box:
        raise box["error"]
    out = box["out"]
    counters = out["metrics"]["counters"]
    compiled = out["metrics"]["compile"]["counters"]
    checks = {
        "all_200": [s for s, _ in out["replies"]] == [200] * requests,
        "records_well_formed": all(
            isinstance(d, dict)
            and _well_formed(d.get("detections"), scfg.NUM_CLASSES)
            for _, d in out["replies"]),
        "warmup_completed": counters["warmup_programs"] > 0,
        "no_recompile_after_warmup":
            counters["recompiles"] == counters["warmup_programs"],
        "kernel_in_program": program["tpu_custom_calls"] > 0,
        # the .so the parent just built must be what answered; only a box
        # without a toolchain may serve on the numpy fallbacks
        "native_active": native.available() == _have_toolchain(),
    }
    return checks, {
        "requests": requests,
        "detections": sum(len(d.get("detections") or ())
                          for _, d in out["replies"]
                          if isinstance(d, dict)),
        "batches": counters.get("batches"),
        "warmup_programs": counters["warmup_programs"],
        "recompiles": counters["recompiles"],
        "aot_hit": compiled["aot_hit"], "aot_miss": compiled["aot_miss"],
        "cache_unavailable": compiled["cache_unavailable"],
        "cache_dir": out["metrics"]["compile"]["cache_dir"],
        "native": native.available(), "program": program,
        "setup_s": out["setup_s"], "steady_s": out["steady_s"]}


def _nms_inputs(n: int, seed: int):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    ctr = rng.rand(n, 2) * 800.0
    wh = rng.rand(n, 2) * 150.0 + 10
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], 1).astype(np.float32)
    scores = np.sort(rng.rand(n).astype(np.float32))[::-1].copy()
    return (jnp.asarray(boxes), jnp.asarray(scores),
            jnp.asarray(rng.rand(n) > 0.02))


@_phase("kernels")
def phase_kernels(device, shapes=NMS_SHAPES, seeds=NMS_SEEDS):
    """Kernel-vs-oracle equality where it can only be shown: on the chip
    (the on-chip half of scripts/check_pallas.py, at the two production
    contracts).  Goes through the PUBLIC ``nms_pallas``, whose lowered
    text says which branch it took."""
    import jax
    import numpy as np

    from mx_rcnn_tpu.kernels.nms_pallas import nms_pallas
    from mx_rcnn_tpu.ops.nms import nms_padded

    t0 = time.perf_counter()
    lowered_with_kernel, mismatches, cases = True, [], 0
    for n, max_out in shapes:
        kw = dict(max_out=max_out, iou_thresh=0.7)
        for seed in range(seeds):
            boxes, scores, valid = _nms_inputs(n, seed)
            if seed == 0:
                text = nms_pallas.lower(boxes, scores, valid=valid,
                                        **kw).as_text()
                lowered_with_kernel &= "tpu_custom_call" in text
            ki_p, km_p = jax.device_get(
                nms_pallas(boxes, scores, valid=valid, **kw))
            ki_r, km_r = jax.device_get(
                nms_padded(boxes, scores, valid=valid, **kw))
            cases += 1
            if not (km_p.sum() == km_r.sum()
                    and np.array_equal(ki_p[km_p], ki_r[km_r])):
                mismatches.append([n, max_out, seed, int(km_p.sum()),
                                   int(km_r.sum())])
    checks = {"kernel_in_program": lowered_with_kernel,
              "kernel_equals_nms_padded": not mismatches}
    return checks, {"cases": cases, "mismatches": mismatches,
                    "run_s": round(time.perf_counter() - t0, 3)}


PHASES = {"train": phase_train, "serve": phase_serve,
          "kernels": phase_kernels, "dp": phase_dp}


def run_phase(name: str) -> int:
    """A child's whole life: one phase, then exit with its verdict.  An
    exception is not caught — the traceback and a non-zero exit are the
    report."""
    return 0 if PHASES[name]()["ok"] else 1


def _have_toolchain() -> bool:
    return bool(shutil.which("make") and shutil.which("g++"))


def build_native() -> None:
    """Rebuild ``libmxr_native.so`` from the committed source, stale file
    removed first: the .so is git-ignored yet may sit in a working tree,
    and a run must not load a binary git would never commit.  A failing
    build on a box that has the toolchain raises; without one the library
    stays absent and the serve phase reports ``native: false``."""
    ndir = os.path.join(REPO, "mx_rcnn_tpu", "native")
    with contextlib.suppress(FileNotFoundError):
        os.remove(os.path.join(ndir, "libmxr_native.so"))
    if _have_toolchain():
        subprocess.run(["make", "-C", ndir], check=True,
                       stdout=subprocess.DEVNULL, timeout=300)


def _child_argv(name: str) -> list:
    return [sys.executable, "-c",
            f"import sys, chip_smoke; "
            f"sys.exit(chip_smoke.run_phase({name!r}))"]


def _run_child(name: str, timeout: float):
    """Run one phase in a process of its own → (exit code, its JSON line
    or None).  The child's stdout is passed through once it ends; a child
    that outlives ``timeout`` is killed with everything it started."""
    proc = subprocess.Popen(_child_argv(name), cwd=REPO, text=True,
                            stdout=subprocess.PIPE, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=max(timeout, 1.0))
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
    sys.stdout.write(out)
    sys.stdout.flush()
    doc = None
    for line in out.splitlines():
        with contextlib.suppress(ValueError):
            parsed = json.loads(line)
            if isinstance(parsed, dict) and parsed.get("phase") == name:
                doc = parsed
    return proc.returncode, doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the data-parallel train phase and its "
                         "one-chip comparison, on a four-chip host")
    args = ap.parse_args(argv)
    names = ("dp",) if args.chips == 4 else ("train", "serve", "kernels")
    deadline = time.monotonic() + BUDGET_S
    ok, device = True, {"platform": None, "kind": None, "count": 0}
    for name in names:
        if name == "serve":
            build_native()
        rc, doc = _run_child(name, deadline - time.monotonic())
        if doc is not None:
            device = doc["device"]
        if rc != 0 or doc is None or not doc["ok"]:
            ok = False
            break
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
