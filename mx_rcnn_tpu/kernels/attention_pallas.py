"""Pallas TPU blocked attention with decomposed relative position terms —
the global blocks of a plain-ViT trunk (``models/vit.py``).

A global block attends over the whole S × S token grid (64 × 64 = 4096
tokens at the served 1024 × 1024): one head's scores are N × N = 16.8 M
numbers, 96 heads a batch of 8 — 6.4 GB in float32, which a 16 GB chip
cannot hold beside the rest of the predict program.  The kernel never
writes them: a tile of queries meets the keys a block at a time under an
online softmax (running max, running sum, rescaled accumulator), so a
(block_q, block_k) tile of scores lives in VMEM and nowhere else.

**The relative terms ride in the matmul.**  ViTDet's attention is
``softmax(s·q kᵀ + Bh + Bw)`` with ``Bh[(y,x),(y',x')] = q[y,x]·Rh[y−y'+S−1]``
and the same along x: a query's bias depends on the key's row and column
alone.  With ``rel_h[n, j] = q[n]·Rh[y(n)−j+S−1]`` (computed outside, a
small einsum) the score is one dot product of longer rows:

    [s·q | rel_h | rel_w] · [k | onehot(y') | onehot(x')]

so the kernel's q rows are D + 2S wide (192 at D = S = 64), its key
columns are ``kᵀ`` stacked on a constant 0/1 matrix, and no bias tile is
ever built or added on the vector unit.  The 0/1 rows are written under
``kᵀ`` into a VMEM scratch once a head (q tile 0); keys and values of a
head stay resident across its q tiles (their block index does not change,
so Pallas does not fetch them again).

Inputs in the dtype they arrive in (bfloat16 in the served program),
scores, softmax statistics and the accumulator in float32.

Off TPU (the CPU test mesh) ``attention`` computes the same function with
materialised scores — Mosaic kernels only lower on TPU;
``tests/test_vitdet.py`` runs the kernel in interpret mode against
that plain form and ``tests/test_tpu_kernels.py``'s pattern compiles it for
a described v5e.  The backward pass differentiates the plain form (the
training recipe of this trunk is not built; one step at a small size is
what the tests hold).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "vit_global_attention"
BLOCK_Q = 512
BLOCK_K = 1024


def rel_terms(q, rel_h, rel_w, grid: int):
    """q (..., N, D) unscaled, N = grid², row-major; rel_h / rel_w
    (2·grid − 1, D) -> (..., N, 2·grid): [q·Rh[y − j + S − 1] for j | the
    same along x]."""
    s = grid
    idx = np.arange(s)[:, None] - np.arange(s)[None, :] + s - 1   # [y, j]
    qg = q.reshape(q.shape[:-2] + (s, s, q.shape[-1]))
    th = rel_h[idx].astype(q.dtype)                               # (S, S, D)
    tw = rel_w[idx].astype(q.dtype)
    bh = jnp.einsum("...yxc,yjc->...yxj", qg, th,
                    preferred_element_type=jnp.float32)
    bw = jnp.einsum("...yxc,xjc->...yxj", qg, tw,
                    preferred_element_type=jnp.float32)
    return jnp.concatenate([bh, bw], axis=-1).reshape(
        q.shape[:-1] + (2 * s,))


def key_positions(grid: int) -> np.ndarray:
    """(2·grid, grid²) 0/1: row j marks the keys of grid row j, row
    grid + j those of grid column j."""
    y, x = np.divmod(np.arange(grid * grid), grid)
    j = np.arange(grid)[:, None]
    return np.concatenate([y[None] == j, x[None] == j]).astype(np.float32)


def attention_plain(q, k, v, rel, grid: int, scale: float):
    """softmax(scale·q kᵀ + Bh + Bw) v with the scores materialised:
    q, k, v (..., N, D); rel (..., N, 2·grid) from ``rel_terms``."""
    s = jnp.einsum("...nd,...md->...nm", q, k,
                   preferred_element_type=jnp.float32) * scale
    bias = (rel[..., :grid, None] + rel[..., None, grid:]).reshape(
        rel.shape[:-1] + (grid * grid,))
    p = jax.nn.softmax(s + bias.astype(jnp.float32), axis=-1)
    return jnp.einsum("...nm,...md->...nd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _kernel(qx_ref, kt_ref, v_ref, pos_ref, o_ref, kx_ref, *, block_k: int):
    d = kt_ref.shape[1]
    n = kt_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():                      # this head's extended keys, once
        kx_ref[:d, :] = kt_ref[0]
        kx_ref[d:, :] = pos_ref[...]

    qx = qx_ref[0]                                       # (bq, D + 2S)
    bq = qx.shape[0]

    def body(i, carry):
        m, l, acc = carry
        ks = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
        s = jnp.dot(qx, kx_ref[:, ks],
                    preferred_element_type=jnp.float32)  # (bq, bk)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v_ref.dtype), v_ref[0, ks, :],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n // block_k, body,
        (jnp.full((bq, 1), -jnp.inf, jnp.float32),
         jnp.zeros((bq, 1), jnp.float32), jnp.zeros((bq, d), jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def attention_blocked(q, k, v, rel, grid: int, scale: float, *,
                      block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                      interpret: bool = False):
    """The kernel: q, k, v (G, N, D), rel (G, N, 2·grid) -> (G, N, D).
    N = grid² a multiple of both block sizes."""
    g, n, d = q.shape
    block_q, block_k = min(block_q, n), min(block_k, n)
    assert n == grid * grid and n % block_q == 0 and n % block_k == 0, \
        (q.shape, grid, block_q, block_k)
    dt = q.dtype
    qx = jnp.concatenate([q.astype(jnp.float32) * scale,
                          rel.astype(jnp.float32)], axis=-1).astype(dt)
    kt = jnp.swapaxes(k, 1, 2)                              # (G, D, N)
    pos = jnp.asarray(key_positions(grid), dt)              # (2S, N)
    dx = d + 2 * grid
    return pl.pallas_call(
        partial(_kernel, block_k=block_k),
        grid=(g, n // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dx), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, d, n), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, n, d), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((2 * grid, n), lambda h, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n, d), dt),
        scratch_shapes=[pltpu.VMEM((dx, n), dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name=KERNEL_NAME,
    )(qx, kt, v, pos)


def _fits(n: int, grid: int) -> bool:
    """The kernel's tiling: whole key blocks of 128 lanes or more, and the
    0/1 rows a whole number of sublane tiles."""
    return n % 128 == 0 and (2 * grid) % 16 == 0


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention_tpu(q, k, v, rel, grid, scale):
    return attention_blocked(q, k, v, rel, grid, scale)


def _fwd(q, k, v, rel, grid, scale):
    return attention_blocked(q, k, v, rel, grid, scale), (q, k, v, rel)


def _bwd(grid, scale, res, g):
    _, vjp = jax.vjp(lambda *a: attention_plain(*a, grid, scale), *res)
    return vjp(g)


_attention_tpu.defvjp(_fwd, _bwd)


def attention(q, k, v, rel_h, rel_w, grid: int, scale: float):
    """q, k, v (B, H, N, D) with N = grid² tokens in row-major order;
    rel_h / rel_w (2·grid − 1, D), a block's own -> (B, H, N, D).  On a TPU,
    at a size the kernel tiles, the blocked kernel; otherwise the plain
    form."""
    rel = rel_terms(q, rel_h, rel_w, grid)
    b, h, n, d = q.shape
    if jax.default_backend() != "tpu" or not _fits(n, grid):
        return attention_plain(q, k, v, rel, grid, scale)
    flat = lambda a: a.reshape((b * h,) + a.shape[2:])  # noqa: E731
    return _attention_tpu(flat(q), flat(k), flat(v), flat(rel), grid,
                          scale).reshape(b, h, n, d)
