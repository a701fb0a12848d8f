"""Non-overlapping 2x2/2 max pooling via reshape+max — MEASURED NEUTRAL,
kept as a reproduction harness, NOT used by the model path.

Hypothesis (round 4): XLA lowers ``nn.max_pool``'s backward to
``select-and-scatter`` (~1.4 ms across the two live VGG16 pools), the
classically slow TPU pool transpose; for non-overlapping 2x2/2 windows a
reshape+max formulation gets an equality-select backward instead.

Measured on TPU v5-lite in round 4 (scripts/bench_pool.py; the logs were
deleted in PR 21, so these are claims to check):
the swap is device-NEUTRAL — VGG16 step 17.336 ms (reshape) vs
17.333 ms (reduce_window); isolated bwd 5.80/6.83 ms (reshape, two pool
shapes) vs 6.53/6.26 ms (reduce_window).  The scatter's cost here equals
the equality-select's, so ``VGGConv`` keeps ``nn.max_pool`` — its
select-and-scatter backward routes tie gradients to the first window
maximum like the reference's cudnn max-pool bwd routes to the recorded
argmax, while this form would split ties evenly (relu-zero ties, the
common bf16 case, are killed upstream by relu's zero gradient either
way).  Retry on a libtpu upgrade only if select-and-scatter regresses.

Reference: MXNet Pooling (pool_type='max', 2x2/2) in ``get_vgg_conv``
(symbol_vgg.py) — blocks 1-4 of the VGG16 body.
"""

import jax.numpy as jnp


def max_pool_2x2(x: jnp.ndarray) -> jnp.ndarray:
    """Max-pool NHWC ``x`` with 2x2 windows, stride 2, VALID padding.

    Forward bit-equal to ``nn.max_pool(x, (2, 2), strides=(2, 2))``; odd
    H/W trailing rows/cols are dropped (floor), matching reduce_window's
    VALID-window semantics without any padding value entering a max.
    """
    n, h, w, c = x.shape
    he, we = h - (h % 2), w - (w % 2)
    if (he, we) != (h, w):
        x = x[:, :he, :we, :]
    x = x.reshape(n, he // 2, 2, we // 2, 2, c)
    return jnp.max(x, axis=(2, 4))
