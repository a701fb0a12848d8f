"""Online inference serving — the first ONLINE workload on the stack.

Layered on the eval machinery, nothing duplicated: requests go through the
loader's image-prep chain (``data.prepare_image``), the ``Predictor``'s
jitted bucket programs, and the shared ``ops/postprocess`` block that
``pred_eval`` scores with.

* ``engine``     — async queue + bucket-aware dynamic batcher (deadline
  flush, partial-batch padding, bounded-queue backpressure).
* ``frontend``   — stdlib HTTP endpoints (``/predict``, ``/healthz``,
  ``/readyz``, ``/metrics``) over TCP or a Unix socket, plus stdio.
* ``warmup``     — eager compilation of every (bucket, batch) program so
  the first request never pays XLA compile; completion = readiness.
* ``controller`` — SLO-driven admission control: adapts per-bucket flush
  batch/delay toward ``--target-p99-ms`` off the engine's own latency
  histograms and sheds load when the queue trend predicts misses.
* ``replica``    — the replica-side of the multi-replica plane: child
  main loop, zero-downtime checkpoint hot-reload with canary rollback,
  checkpoint watching, and the ``MXR_FAULT_REPLICA_*`` chaos injectors.
* ``supervisor`` — the parent-side: liveness/readiness probing, crash/
  hang detection, backoff respawn with a systemic limit, rolling
  reloads, and the retry-budgeted request router.
* ``stream``     — sequenced-frame streaming over the same batcher:
  per-stream state (reference frame + cached detections), cross-stream
  temporal coalescing (same-bucket frames from different streams share
  one ``serve_e2e`` dispatch), and an on-device ``frame_delta`` skip
  gate that answers low-motion frames from cache without any forward.
* ``pool``       — multi-model serving: N ``(config, params, Predictor)``
  entries behind one frontend (``/predict?model=...``), a single
  cross-model dispatcher interleaving per-model bucket queues by queue
  depth × SLO class, and a device weight-residency manager paging param
  trees host↔device under a byte budget (LRU, pinning, zero recompiles
  — params are runtime arguments to every program).  Also home of the
  cascade router (``--cascade small:big``): requests answer from the
  cheap model unless an on-device confidence gate — the flywheel
  miner's hardness, computed from the still-on-device detections —
  escalates them to the big model with their staged pixels reused.
* ``fabric``     — the cross-host generalization: a transport-agnostic
  replica pool (local fork children + remote TCP members that ``--join``
  or are registered by address), HTTP-probe-driven membership with
  eviction/re-admission instead of respawn, least-loaded routing over
  freshness-checked queue-depth gauges, per-member circuit breakers,
  request hedging, partition-tolerant degraded serving, and rolling
  hot-reload across remote members.
* ``autoscaler`` — the capacity authority over the fabric: forecasts
  demand from the pool's queue-depth gauges (PR-6 least-squares slope),
  scales the fleet between configured bounds through existing surfaces
  only (supervisor on-demand spawn/retire, member park/unpark via the
  register path, model-pool residency rebalance), with hysteresis,
  per-direction cooldowns, a thrash-freeze guard, and a zero-recompile
  assertion over registry counters on every scale event.

Driver: top-level ``serve.py`` (``--replicas N`` for the plane).  Speed
is measured by the benchmark alone (``benchmark/README.md``); behaviour
is pinned by ``tests/test_serve.py``, ``tests/test_slo.py`` and
``tests/test_replica.py``.
"""

from mx_rcnn_tpu.serve.autoscaler import (AutoscalerOptions,
                                          CapacityAuthority,
                                          fleet_compile_counters,
                                          fleet_compiled_programs)
from mx_rcnn_tpu.serve.controller import ControllerOptions, SLOController
from mx_rcnn_tpu.serve.engine import (DeadlineExceededError, RejectedError,
                                      ServeEngine, ServeFuture, ServeOptions)
from mx_rcnn_tpu.serve.fabric import (CircuitBreaker, FabricOptions,
                                      FabricRouter, LocalMember, RemoteMember,
                                      ReplicaPool, make_fabric_server,
                                      normalize_address, register_with_router)
from mx_rcnn_tpu.serve.frontend import (address_request, address_request_raw,
                                        encode_image_payload, make_server,
                                        parse_address, run_stdio,
                                        run_stream_stdio,
                                        tcp_http_request, tcp_http_request_raw,
                                        unix_http_request,
                                        unix_http_request_raw)
from mx_rcnn_tpu.serve.pool import (FIDELITY_CLASSES, CascadeFuture,
                                    CascadeRouter, ModelEntry, ModelPool,
                                    param_nbytes)
from mx_rcnn_tpu.serve.replica import (CheckpointWatcher, NetFaults,
                                       ReplicaFaults, make_reloader,
                                       reload_engine_params,
                                       scan_checkpoints, serve_replica)
from mx_rcnn_tpu.serve.supervisor import (ReplicaRouter, ReplicaSpec,
                                          ReplicaSupervisor,
                                          SupervisorOptions,
                                          make_router_server, replica_specs)
from mx_rcnn_tpu.serve.stream import (FrameResult, StaleSeqError,
                                      StreamManager, StreamOptions)
from mx_rcnn_tpu.serve.warmup import warmup

__all__ = ["ServeEngine", "ServeOptions", "ServeFuture", "RejectedError",
           "DeadlineExceededError", "SLOController", "ControllerOptions",
           "make_server", "run_stdio", "unix_http_request",
           "unix_http_request_raw", "encode_image_payload", "warmup",
           "CheckpointWatcher", "ReplicaFaults", "make_reloader",
           "reload_engine_params", "scan_checkpoints", "serve_replica",
           "ReplicaRouter", "ReplicaSpec", "ReplicaSupervisor",
           "SupervisorOptions", "make_router_server", "replica_specs",
           "CircuitBreaker", "FabricOptions", "FabricRouter", "LocalMember",
           "RemoteMember", "ReplicaPool", "make_fabric_server",
           "normalize_address", "register_with_router", "NetFaults",
           "parse_address", "address_request", "address_request_raw",
           "tcp_http_request", "tcp_http_request_raw",
           "StreamManager", "StreamOptions", "StaleSeqError",
           "FrameResult", "run_stream_stdio",
           "ModelPool", "ModelEntry", "param_nbytes",
           "CascadeRouter", "CascadeFuture", "FIDELITY_CLASSES",
           "AutoscalerOptions", "CapacityAuthority",
           "fleet_compile_counters", "fleet_compiled_programs"]
