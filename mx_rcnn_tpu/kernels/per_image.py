"""Running a single-image Mosaic kernel over a batch of images.

The kernels' ``custom_vmap`` rules share this: Mosaic cannot auto-batch
their SMEM / scratch block specs (a squeezed leading dim violates the
(8, 128) block-shape rule) and the sweeps are sequential per image anyway,
so a batch level becomes one serial ``lax.map`` over single-image calls.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

from mx_rcnn_tpu.parallel.mesh import batch_axes_of


def map_images(fn, args: tuple):
    """``lax.map`` of ``fn(*row)`` over the leading axis of ``args``.

    XLA cannot partition a Mosaic kernel either, and jax refuses to lower
    one for several devices outside a ``shard_map``.  So inside a step
    traced for a mesh (``MeshPlan.traced`` makes the mesh ambient) the map
    runs under ``shard_map`` with every mesh axis manual: each device maps
    over its own rows of the batch.  A leading axis the batch axes do not
    divide (the per-class level of a nested vmap) is computed whole on
    every device instead.  Already inside such a ``shard_map`` (the outer
    level of a nested vmap), or with no mesh, it is the plain map."""
    def per_device(*rows):
        return jax.lax.map(lambda row: fn(*row), rows)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return per_device(*args)
    axes = batch_axes_of(mesh)
    n = math.prod(mesh.shape[a] for a in axes)
    spec = P(axes) if args[0].shape[0] % n == 0 else P()
    return jax.shard_map(per_device, mesh=mesh,
                         in_specs=(spec,) * len(args), out_specs=spec,
                         check_vma=False)(*args)
