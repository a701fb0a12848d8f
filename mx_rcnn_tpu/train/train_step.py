"""The jitted SPMD train step.

One function replaces the reference's per-step machinery (SURVEY §3.1):
Module forward/backward per GPU, ProposalTarget's device→host→device sync
(eliminated — sampling is in-graph), KVStore gradient push/pull (XLA
all-reduce over the mesh data axis), SGD update, metric readback (six
scalars, one transfer).

The step is ``jax.jit``-ed with explicit shardings: batch over the data
axis, state replicated.  XLA inserts the gradient ``psum`` where the
KVStore reduce used to be; donation reuses the state buffers in place.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.parallel.mesh import (MeshPlan, check_spatial,
                                       stack_sharding)
from mx_rcnn_tpu.train.metric import metric_scalars
from mx_rcnn_tpu.train.optim import make_optimizer


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Replicated training state (params + momentum + step counter)."""

    step: jnp.ndarray
    params: Any
    opt_state: Any


def create_train_state(cfg: Config, params, steps_per_epoch: int,
                       begin_epoch: int = 0,
                       fixed_prefixes=None):
    """-> (TrainState, tx, trainable_mask).  Pass the mask to
    ``make_train_step`` so frozen subtrees are stop_gradient-ed (XLA then
    dead-code-eliminates their whole backward chain instead of computing
    gradients the optimizer would zero anyway)."""
    # copy params into the state: the jitted step donates its state, and
    # aliasing the caller's buffers would delete them after the first step
    # (the alternate-training driver reuses one init tree across stages)
    params = jax.tree.map(lambda x: jnp.array(x, copy=True), params)
    tx, _, mask = make_optimizer(cfg, steps_per_epoch, params,
                                 begin_epoch=begin_epoch,
                                 fixed_prefixes=fixed_prefixes)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=tx.init(params)), tx, mask


def _loss_fn(params, model, batch, key, graph: str):
    """Dispatch to the model's training graph: 'end2end' | 'rpn' | 'rcnn'."""
    if graph == "end2end":
        kwargs = {}
        if "gt_masks" in batch:
            kwargs["gt_masks"] = batch["gt_masks"]
        total, aux = model.apply(
            {"params": params}, batch["images"], batch["im_info"],
            batch["gt_boxes"], batch["gt_classes"], batch["gt_valid"], key,
            rngs={"dropout": jax.random.fold_in(key, 1)}, **kwargs)
    elif graph == "rpn":
        total, aux = model.apply(
            {"params": params}, batch["images"], batch["im_info"],
            batch["gt_boxes"], batch["gt_valid"], key,
            method=type(model).rpn_train)
    elif graph == "rcnn":
        total, aux = model.apply(
            {"params": params}, batch["images"], batch["im_info"],
            batch["rois"], batch["roi_valid"], batch["gt_boxes"],
            batch["gt_classes"], batch["gt_valid"], key,
            method=type(model).rcnn_train,
            rngs={"dropout": jax.random.fold_in(key, 1)})
    else:
        raise ValueError(f"unknown graph '{graph}'")
    return total, aux


def _all_finite(total, grads):
    """On-device scalar: loss AND every gradient leaf finite (the NaN
    sentinel — one cheap fused reduction per leaf, no host sync)."""
    flags = [jnp.isfinite(total)]
    flags += [jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)]
    return jnp.all(jnp.stack(flags))


def _build_step(model, tx: optax.GradientTransformation, graph: str,
                trainable_mask, sentinel: bool = False,
                skip_nonfinite: bool = False) -> Callable:
    """The raw (un-jitted) train step shared by ``make_train_step`` and
    ``make_multi_train_step``: loss+grad, frozen-subtree stop_gradient,
    optimizer update, metric scalars, step counter.

    ``sentinel`` adds an on-device all-finite flag over (loss, grads) to
    the metrics (``all_finite``) — fetched by the trainer at Speedometer
    cadence, it drives the NaN policies without a per-step host sync.
    ``skip_nonfinite`` (the ``skip`` policy) additionally guards the
    update in-graph: a non-finite step keeps the previous params AND
    optimizer state (only the step counter advances), so params can never
    be poisoned in the window before the host notices.
    """

    def step(state: TrainState, batch, key):
        def loss_fn(params):
            if trainable_mask is not None:
                params = jax.tree.map(
                    lambda v, t: v if t else jax.lax.stop_gradient(v),
                    params, trainable_mask)
            return _loss_fn(params, model=model, batch=batch, key=key,
                            graph=graph)

        (total, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = metric_scalars(aux)
        metrics["total_loss"] = total
        if sentinel:
            finite = _all_finite(total, grads)
            metrics["all_finite"] = finite.astype(jnp.float32)
            if skip_nonfinite:
                keep = lambda new, old: jnp.where(finite, new, old)
                params = jax.tree.map(keep, params, state.params)
                opt_state = jax.tree.map(keep, opt_state, state.opt_state)
        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state)
        return new_state, metrics

    return step


def make_train_step(model, tx: optax.GradientTransformation,
                    plan: Optional[MeshPlan] = None,
                    graph: str = "end2end",
                    donate: bool = True,
                    trainable_mask=None,
                    sentinel: bool = False,
                    skip_nonfinite: bool = False) -> Callable:
    """Build ``train_step(state, batch, key) -> (state, metrics)``.

    With a ``MeshPlan``, inputs/outputs carry NamedShardings (batch split on
    the data axis, state replicated) — the whole of data parallelism; no
    pmap, no hand-written collectives.  Without one, plain single-device jit
    (the reference's 1-GPU path).

    ``trainable_mask`` (the tree from ``create_train_state``; True =
    trainable): frozen leaves are ``stop_gradient``-ed inside the loss, so
    their gradients are structural zeros and XLA dead-code-eliminates the
    frozen backward tail entirely (the reference freezes conv1+stage1 —
    ``fixed_param_prefix`` — but still computed those gradients; we don't).

    ``sentinel``/``skip_nonfinite``: the NaN sentinel / in-graph
    non-finite-update guard (see ``_build_step``; driven by
    ``resilience.ResilienceOptions.nan_policy``).
    """
    if plan is not None:
        # thin-shard guard at the mechanism level: every spatially-sharded
        # step (fit, dryrun, direct callers) compiles through here
        check_spatial(plan, model.cfg)

    step = _build_step(model, tx, graph, trainable_mask,
                       sentinel=sentinel, skip_nonfinite=skip_nonfinite)
    if plan is None:
        return jax.jit(step, donate_argnums=(0,) if donate else ())
    return _jit_planned(step, plan, donate)


def _jit_planned(fn, plan: MeshPlan, donate: bool, wrap=lambda sh: sh):
    """jit ``fn(state, batch, key)`` with the plan's shardings — the one
    wiring shared by the single-step and multi-step makers (``wrap``
    lifts each batch sharding; the multi-step maker passes
    ``stack_sharding`` to prepend the unsharded stack axis).

    For tensor parallelism (MeshPlan.param_shardings on the head FCs)
    and/or spatial parallelism (image height over the space axis), the
    state sharding tree is structural and the batch sharding tree
    depends on the batch's keys, so both are built lazily from the first
    call and the jitted fn cached — keyed on the batch's key set: the
    spatial in_shardings are a per-key dict, so a batch gaining/losing
    an optional key (gt_masks) must get its own jitted entry, not a
    pytree structure mismatch at dispatch."""
    repl = plan.replicated()
    batch_sh = wrap(plan.batch())
    fn = plan.traced(fn)   # mesh ambient: Mosaic kernels shard_map themselves
    if plan.n_model > 1 or plan.n_space > 1:
        cache = {}

        def stepper(state, batch, key):
            ck = frozenset(batch) if plan.n_space > 1 else "fn"
            jitted = cache.get(ck)
            if jitted is None:
                st_sh = plan.state_shardings(state)
                b_sh = ({k: wrap(plan.images()) if k == "images" else batch_sh
                         for k in batch}
                        if plan.n_space > 1 else batch_sh)
                jitted = jax.jit(
                    fn,
                    in_shardings=(st_sh, b_sh, repl),
                    out_shardings=(st_sh, repl),
                    donate_argnums=(0,) if donate else (),
                )
                cache[ck] = jitted
            return jitted(state, batch, key)

        return stepper
    return jax.jit(
        fn,
        in_shardings=(repl, batch_sh, repl),
        out_shardings=(repl, repl),
        donate_argnums=(0,) if donate else (),
    )

def make_multi_train_step(model, tx: optax.GradientTransformation, k: int,
                          plan: Optional[MeshPlan] = None,
                          graph: str = "end2end",
                          donate: bool = True,
                          trainable_mask=None,
                          unroll: Optional[bool] = None,
                          sentinel: bool = False,
                          skip_nonfinite: bool = False) -> Callable:
    """``k`` train steps in ONE dispatched program: ``lax.scan`` over
    batches stacked on a leading axis (every leaf shaped (k, ...)).

    Why this exists (round 4, measured): dispatching one program per step
    pays a per-dispatch cost — host RPC on remote devices, and, less
    obviously, a per-program compilation horizon: profiled on v5-lite,
    XLA compiles the FPN step to 21.95 ms standalone but 17.85 ms as a
    loop body (it picks a better layout for the P2-resolution neck convs
    when the program is a loop — r4_tpu_session7.log, validated with
    per-iteration-varying data and asserted step counts).  Scanning the
    step is also the idiomatic JAX recipe for small steps.  ``fit(...,
    steps_per_dispatch=k)`` feeds this from the real loader by stacking
    k consecutive batches.

    Semantics vs k sequential ``make_train_step`` calls: identical math
    per step (same ``_build_step``); the per-step rng keys are
    ``fold_in(key, i)`` for i in [0, k); the returned metrics are the
    MEAN over the k steps (the per-step values feed the same MetricBank
    averaging that single-step fit samples at Speedometer cadence).
    Parity is tested in tests/test_train.py.

    ``unroll``: pass ``unroll=k`` to ``lax.scan`` (straight-line body
    repetition instead of a compiled loop).  Default: unrolled on the CPU
    backend, rolled loop elsewhere.  Values are identical either way
    (same scan semantics); the split exists because XLA:CPU's compile
    time for a scan-of-train-step under SPMD is pathological — measured
    round 5: >17 min at 8 partitions and >25 min in one 2-partition
    config on a host that compiles the same step standalone in 29 s —
    while on TPU the rolled loop is both fine to compile and the point
    of the feature (the loop-body layout win, above)."""
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    if unroll is None:
        unroll = jax.default_backend() == "cpu"
    if plan is not None:
        check_spatial(plan, model.cfg)
    step = _build_step(model, tx, graph, trainable_mask,
                       sentinel=sentinel, skip_nonfinite=skip_nonfinite)

    def multi(state: TrainState, batches, key):
        if k == 1:
            # no scan at k=1: same values (fold_in(key, 0); mean over one
            # step is identity), and the scan construct itself is what
            # XLA:CPU compiles pathologically under SPMD (unroll=k cannot
            # help a length-1 loop)
            return step(state, jax.tree.map(lambda x: x[0], batches),
                        jax.random.fold_in(key, 0))

        def body(st, xs):
            i, b = xs
            return step(st, b, jax.random.fold_in(key, i))

        state, ms = jax.lax.scan(body, state, (jnp.arange(k), batches),
                                 unroll=k if unroll else 1)
        return state, jax.tree.map(lambda x: jnp.mean(x, axis=0), ms)

    if plan is None:
        return jax.jit(multi, donate_argnums=(0,) if donate else ())
    return _jit_planned(multi, plan, donate, wrap=stack_sharding)
