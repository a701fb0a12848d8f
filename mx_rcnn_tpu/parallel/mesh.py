"""Mesh construction + sharding plans.

The reference's single parallelism strategy is data parallelism
(SURVEY §2.3): ``Module`` splits each host batch across ``ctx = [mx.gpu(i)]``
and ``KVStore('device')`` all-reduces gradients over PCIe/NVLink.  Here the
same strategy is a named mesh axis:

* ``data`` — batch axis.  Gradients are all-reduced over it by XLA (the
  collective rides ICI within a slice, DCN across slices when the axis spans
  slices).
* ``model`` — reserved model axis (size 1 in the reference configs; the
  mesh abstraction keeps it open for sharding large backbones / FPN heads —
  an intentional extension point, not a reference capability).
* ``space`` — spatial-parallel axis (``make_mesh(space=N)``): the image
  HEIGHT dimension shards over it, so the conv body runs on H-slices with
  XLA/GSPMD inserting the halo exchanges every 3×3/stride conv needs —
  the detection analogue of sequence/context parallelism for inputs too
  large for one chip's HBM (aerial/medical tiles).  Where the graph stops
  being spatially shardable (the per-image proposal sort/NMS and the RoI
  head), GSPMD's propagation inserts the gather; compute up to c4 — 90%
  of the FLOPs (SURVEY §3.5) — stays sharded.  Like ``model``, an
  extension beyond the reference's DP-only strategy.

Everything here is plain `jax.sharding`; no pmap.  A jitted step whose
inputs carry these shardings gets its collectives inserted by XLA — the
TPU equivalent of the KVStore push/pull in the reference call stack
(SURVEY §3.1 "KVStore push/pull gradient reduce").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_axes_of(mesh) -> tuple:
    """The mesh axes the batch shards over: every axis that is neither
    ``model`` nor ``space`` (so ``dcn`` and ``data``).  Takes a concrete
    or an abstract mesh."""
    return tuple(n for n in mesh.axis_names if n not in ("model", "space"))


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A mesh plus the shardings the train/eval steps use.

    Axis convention: an optional leading ``dcn`` axis (slice-crossing, for
    multi-slice jobs), then ``data`` (ICI within a slice), then ``model``.
    The batch shards over every batch axis present, so a multi-slice
    gradient all-reduce decomposes into an ICI reduce within each slice
    plus a DCN reduce across slices — XLA picks the hierarchical schedule
    from the mesh's device order (the "How to Scale Your Model" recipe:
    name the axes, annotate, let XLA place collectives).
    """

    mesh: Mesh

    @property
    def batch_axes(self) -> tuple:
        return batch_axes_of(self.mesh)

    def traced(self, fn):
        """``fn``, on its way into ``jax.jit``, wrapped so that it is
        traced with this plan's mesh ambient
        (``jax.sharding.get_abstract_mesh``).  XLA partitions everything
        in a step by itself EXCEPT a Mosaic kernel, which jax refuses to
        lower for more than one device outside a ``shard_map``; the
        ambient mesh is how the kernels' batching rules
        (``kernels/per_image.py``) know to wrap themselves in one.  The
        axes stay Auto, so nothing else about the trace changes."""
        abstract = self.mesh.abstract_mesh

        @functools.wraps(fn)
        def under_mesh(*args, **kwargs):
            with jax.sharding.use_abstract_mesh(abstract):
                return fn(*args, **kwargs)

        return under_mesh

    @property
    def n_data(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    def batch(self) -> NamedSharding:
        """Leading-axis (batch) sharding over all batch axes (dcn, data)."""
        return NamedSharding(self.mesh, P(self.batch_axes))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def n_model(self) -> int:
        return self.mesh.shape.get("model", 1)

    @property
    def n_space(self) -> int:
        return self.mesh.shape.get("space", 1)

    def images(self) -> NamedSharding:
        """Sharding for image tensors (B, H, W, C) — batch over the batch
        axes AND height over ``space`` (rows split across chips; GSPMD
        halo-exchanges the conv borders).  Identical to ``batch()`` when
        the mesh has no space axis."""
        if self.n_space <= 1:
            return self.batch()
        return NamedSharding(self.mesh, P(self.batch_axes, "space"))

    # -- tensor parallelism over the head FCs (model axis > 1) --------------
    # The classic Megatron pairing on the RoI-head MLP, which is where the
    # shardable parameters are (VGG fc6 alone is 25088×4096 ≈ 100M params;
    # the FPN box head uses the same fc6/fc7 names): fc6 column-parallel
    # (output features sharded — its bias shards with them; the relu/dropout
    # between the FCs are elementwise on the sharded features), fc7
    # row-parallel (contracts the sharded axis; XLA inserts the psum and
    # the replicated fc7 bias adds after it).  Everything else replicates —
    # conv backbones are data-parallel territory (SURVEY §2.3: DP is the
    # reference's only strategy; the model axis is our extension point).
    _TP_RULES = (
        (("fc6", "kernel"), P(None, "model")),
        (("fc6", "bias"), P("model")),
        (("fc7", "kernel"), P("model", None)),
        (("fc7", "bias"), P()),
    )

    def _tp_rule(self, path):
        names = tuple(getattr(e, "key", getattr(e, "name", str(e)))
                      for e in path)
        for suffix, spec in self._TP_RULES:
            if names[-len(suffix):] == tuple(suffix):
                return NamedSharding(self.mesh, spec)
        return self.replicated()

    def param_shardings(self, params):
        """Sharding tree for a param tree: replicated except the TP rules
        above (no-op mesh without a >1 ``model`` axis → all replicated)."""
        if self.n_model <= 1:
            return jax.tree.map(lambda _: self.replicated(), params)
        return jax.tree_util.tree_map_with_path(
            lambda p, _: self._tp_rule(p), params)

    def state_shardings(self, state):
        """Sharding tree for a TrainState (same pytree structure, shardings
        as leaves — jit's in_shardings/out_shardings form).  Optimizer-state
        leaves match by PATH SUFFIX: optax's momentum trees keep the param
        tree's key path as a suffix (…/trace/head_body/fc6/kernel), so the
        same TP rules apply; scalar counts fall through to replicated."""
        return dataclasses.replace(
            state, step=self.replicated(),
            params=self.param_shardings(state.params),
            opt_state=self.param_shardings(state.opt_state))


def make_mesh(devices: Optional[Sequence[jax.Device]] = None,
              data: Optional[int] = None, model: int = 1,
              space: int = 1,
              axis_names=None) -> MeshPlan:
    """Build a (data, model[, space]) mesh from the visible devices.

    ``data`` defaults to ``len(devices) // (model * space)``.  On a real
    pod slice, device order from `jax.devices()` keeps ICI neighbours
    adjacent, so the inner axes ride ICI — ``space`` is innermost because
    halo exchanges are the most latency-sensitive collective.  For
    multi-slice jobs use ``make_multislice_mesh`` (a leading DCN axis —
    the reference's `dist_sync` kvstore analogue, which upstream left
    unscripted; here it is scripted and tested on the virtual mesh).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if axis_names is None:
        axis_names = (("data", "model", "space") if space > 1
                      else ("data", "model"))
    elif space > 1 and (len(axis_names) != 3 or axis_names[2] != "space"):
        # the device grid below is shaped (data, model, space); caller-
        # supplied names must agree or images silently stop height-sharding
        raise ValueError(
            f"space={space} needs axis_names (data, model, 'space'); "
            f"got {axis_names}")
    if data is None:
        data = len(devices) // (model * space)
    n = data * model * space
    if n > len(devices):
        raise ValueError(f"mesh {data}x{model}x{space} needs {n} devices, "
                         f"have {len(devices)}")
    if n < len(devices):
        # same contract as make_multislice_mesh: an explicit smaller mesh
        # must not silently idle chips — slice the device list yourself
        raise ValueError(
            f"mesh {data}x{model}x{space} uses only {n} of {len(devices)} "
            "devices; pass devices[:n] explicitly if that is intended")
    shape = (data, model, space) if space > 1 else (data, model)
    arr = np.asarray(devices).reshape(shape)
    return MeshPlan(mesh=Mesh(arr, axis_names))


def make_multislice_mesh(devices: Optional[Sequence[jax.Device]] = None,
                         slices: Optional[int] = None,
                         data_per_slice: Optional[int] = None,
                         model: int = 1) -> MeshPlan:
    """Hierarchical data-parallel mesh for multi-slice jobs:
    axes ``(dcn, data, model)`` with ``dcn`` crossing slice boundaries.

    On real multi-slice hardware the slice of each device is read from
    ``device.slice_index`` (devices grouped so DCN is the outer axis and
    ICI neighbours stay adjacent on the inner axes — the layout
    `jax.experimental.mesh_utils.create_hybrid_device_mesh` produces).
    When the runtime exposes no slice topology (single slice, CPU test
    mesh), ``slices`` partitions the device list positionally — that is
    how the multi-slice step compiles and runs on the 8-device virtual
    mesh in tests.

    The train step needs no changes: ``MeshPlan.batch()`` shards the batch
    over (dcn, data) jointly and XLA lowers the gradient all-reduce into
    the within-slice ICI part and the cross-slice DCN part.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)

    slice_ids = [getattr(d, "slice_index", 0) for d in devices]
    n_real = len(set(slice_ids))
    if n_real > 1:  # real multi-slice topology: group by slice
        by_slice: dict = {}
        for d, s in zip(devices, slice_ids):
            by_slice.setdefault(s, []).append(d)
        groups = [by_slice[s] for s in sorted(by_slice)]
        if slices is None:
            slices = len(groups)
        if slices != len(groups):
            raise ValueError(f"requested {slices} slices, topology has {len(groups)}")
        sizes = {len(g) for g in groups}
        if len(sizes) > 1:  # never silently drop a slice's extra chips
            raise ValueError(f"slices are uneven: sizes {sorted(sizes)}; "
                             "pass an explicit device subset")
        per = len(groups[0])
    else:  # positional emulation (single slice / virtual CPU mesh)
        if slices is None:
            raise ValueError("slices required when the runtime exposes no "
                             "slice topology")
        if slices < 1 or len(devices) % slices:
            raise ValueError(f"{len(devices)} devices do not divide into "
                             f"{slices} slices")
        per = len(devices) // slices
        groups = [devices[i * per:(i + 1) * per] for i in range(slices)]
    if data_per_slice is None:
        data_per_slice = per // model
    n = data_per_slice * model
    if n > per:
        raise ValueError(f"slice mesh {data_per_slice}x{model} needs {n} "
                         f"devices per slice, have {per}")
    if n < per:
        # mirrors the uneven-slice error above: an explicit data_per_slice
        # smaller than the slice must not silently idle chips
        raise ValueError(
            f"slice mesh {data_per_slice}x{model} uses only {n} of {per} "
            "devices per slice; pass an explicit device subset if that is "
            "intended")
    arr = np.asarray(groups).reshape(slices, data_per_slice, model)
    return MeshPlan(mesh=Mesh(arr, ("dcn", "data", "model")))


def check_spatial(plan: MeshPlan, cfg) -> None:
    """Reject spatial plans whose height shards would be thinner than a
    stride-2 conv's halo.

    Round-4 finding (virtual CPU mesh, jax 0.9/XLA): when a height-sharded
    stride-2 3×3 conv's input has only ONE row per ``space`` shard, the
    SPMD-partitioned program returns garbage (isolated: a lone conv is
    fine; inside the ResNet bottleneck composite the output is off by O(1)
    — an XLA partitioner bug with halos spanning multiple shards, not a
    rounding effect).  With ≥ 2 rows per shard at every stride-2 input the
    sharded program matches the flat one to f32 rounding (measured 1e-5
    on the full FPN pyramid).  The invariant is ≥ 2 rows/shard at every
    stride-2 input **with a spatial window > 1** (i.e. a halo): the
    deepest such input is C4 (stride 16) for FPN's stage 5, C3 (stride 8)
    for the classic body (whose stage 5 runs on pooled RoIs, not the
    sharded map) — hence ``min SCALES height >= 2 * stride * n_space``.
    FPN's P6 subsample does consume the stride-32 P5 map at 1 row/shard
    inside this envelope, but it is a 1×1-window stride-2 max_pool
    (``models/fpn.py``): each output row reads exactly one input row, no
    halo exchange exists to miscompile, and the H=64 space=2 eval parity
    test (``tests/test_eval_mesh.py``) runs exactly that 1-row/shard P6
    shape and matches the flat program."""
    if plan.n_space <= 1:
        return
    stride = 16 if cfg.network.HAS_FPN else 8
    min_h = min(int(h) for h, _ in cfg.tpu.SCALES)
    need = 2 * stride * plan.n_space
    if min_h < need:
        raise ValueError(
            f"space={plan.n_space} needs image height >= {need} "
            f"(2 rows/shard at the deepest stride-2 conv input, stride "
            f"{stride}); SCALES has height {min_h}.  Thinner shards hit an "
            f"XLA SPMD halo miscompile — see parallel/mesh.py:check_spatial")


def shard_batch(plan: MeshPlan, batch):
    """Place a host batch (pytree of np arrays, leading axis = batch) onto
    the mesh, split over the data axis — the analogue of Module's
    ``work_load_list`` ctx split, minus the host copy per device: a single
    `device_put` with a sharding does the scatter.  On a spatial mesh the
    ``images`` entry additionally splits its height rows over ``space``
    (``MeshPlan.images``).

    On a mesh spanning several processes (multi-host — see
    ``parallel/distributed.py``) each process passes only ITS rows of the
    global batch (the loader's ``num_parts``/``part_index`` slice) and the
    global arrays are assembled per-shard; the single-process fast path is
    one ``device_put`` scatter."""
    from mx_rcnn_tpu.parallel.distributed import (global_from_local,
                                                  is_multiprocess_mesh)

    if is_multiprocess_mesh(plan.mesh):
        return global_from_local(plan, batch)
    sh = plan.batch()
    if isinstance(batch, dict):
        im_sh = plan.images()
        return jax.device_put(
            batch, {k: im_sh if k == "images" else sh for k in batch})
    if plan.n_space > 1:
        raise TypeError(
            "spatial meshes require dict batches (the 'images' key selects "
            f"the height-sharded placement); got {type(batch).__name__}")
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)

def stack_sharding(sh):
    """The same placement with an unsharded leading (stack) axis
    prepended — the one rule for multi-step (k, batch, ...) trees; both
    ``shard_stacked_batch`` and ``make_multi_train_step``'s in_shardings
    derive from here so the two can never diverge."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(sh.mesh, P(None, *sh.spec))


def shard_stacked_batch(plan: MeshPlan, batches):
    """Place a STACK of k host batches (every leaf (k, batch, ...)) onto
    the mesh for ``make_multi_train_step``: the leading stack axis stays
    unsharded, the batch axis splits over the data axes, and ``images``
    additionally splits height over ``space`` when present.  Multi-process
    meshes assemble global arrays from each process's rows, like
    ``shard_batch``."""
    from mx_rcnn_tpu.parallel.distributed import (global_from_local,
                                                  is_multiprocess_mesh)

    if is_multiprocess_mesh(plan.mesh):
        return global_from_local(plan, batches, stacked=True)
    sh = stack_sharding(plan.batch())
    if isinstance(batches, dict):
        im_sh = stack_sharding(plan.images())
        return jax.device_put(
            batches, {k: im_sh if k == "images" else sh for k in batches})
    if plan.n_space > 1:
        raise TypeError(
            "spatial meshes require dict batches (the 'images' key selects "
            f"the height-sharded placement); got {type(batches).__name__}")
    return jax.tree.map(lambda x: jax.device_put(x, sh), batches)
