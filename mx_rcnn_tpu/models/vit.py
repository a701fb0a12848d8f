"""Plain ViT trunk + simple feature pyramid (ViTDet) — the second trunk
``FPNFasterRCNN`` can stand on.

Li, Mao, Girshick, He 2022, "Exploring Plain Vision Transformer Backbones
for Object Detection" (arXiv:2203.16527), sections 3 and 4.1:

* trunk: a 16 × 16 stride-16 patch embedding + an absolute position vector
  a grid cell, then pre-norm blocks ``x += Attn(LN(x)); x += MLP(LN(x))``
  (LN eps 1e-6, exact GELU, no final norm).  Most blocks attend inside
  non-overlapping windows of the (zero-padded) grid; the blocks named in
  ``VIT_GLOBAL_BLOCKS`` attend over the whole grid.  Both kinds carry the
  decomposed relative position terms, computed from the unscaled q
  (``kernels/attention_pallas.py``).
* neck: the *simple feature pyramid* — strides 4, 8, 16, 32 made from the
  last block's single stride-16 map by deconvolutions, identity and a
  max-pool; no top-down path, no lateral sums; each level a 1 × 1 then a
  3 × 3 conv, both without bias and each followed by LN over the channels.
  P6 = P5 subsampled by 2, for the RPN only, as ``FPNNeck`` makes it.

``pos_embed`` is held at the grid of the one bucket the network is built
for (``tpu.SCALES[0]``): a ViT preset serves and evaluates at one square
bucket, and another input size is an error at trace time.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.kernels.attention_pallas import attention

LN_EPS = 1e-6


def window_partition(x, window: int):
    """(B, H, W, C) -> ((B · nH · nW, window, window, C), (Hp, Wp)): the
    grid zero-padded at the bottom and right to whole windows, then cut."""
    b, h, w, c = x.shape
    ph, pw = (-h) % window, (-w) % window
    x = jnp.pad(x, ((0, 0), (0, ph), (0, pw), (0, 0)))
    hp, wp = h + ph, w + pw
    x = x.reshape(b, hp // window, window, wp // window, window, c)
    return (x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, window, window, c),
            (hp, wp))


def window_unpartition(win, window: int, padded_hw: Tuple[int, int],
                       hw: Tuple[int, int]):
    """The inverse: windows back in place, the padding cropped."""
    hp, wp = padded_hw
    b = win.shape[0] // ((hp // window) * (wp // window))
    x = win.reshape(b, hp // window, wp // window, window, window, -1)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, hp, wp, -1)
    return x[:, :hw[0], :hw[1]]


class Attention(nn.Module):
    """Multi-head attention over a square grid of ``grid`` × ``grid``
    tokens with decomposed relative position terms."""

    heads: int
    grid: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, _, c = x.shape
        assert s == self.grid and x.shape[2] == s, (x.shape, self.grid)
        d = c // self.heads
        rel_h = self.param("rel_pos_h", nn.initializers.zeros,
                           (2 * s - 1, d), jnp.float32)
        rel_w = self.param("rel_pos_w", nn.initializers.zeros,
                           (2 * s - 1, d), jnp.float32)
        qkv = nn.Dense(3 * c, dtype=self.dtype, name="qkv")(
            x.reshape(b, s * s, c))
        # columns are [q | k | v][head][d]
        q, k, v = qkv.reshape(b, s * s, 3, self.heads, d).transpose(
            2, 0, 3, 1, 4)
        o = attention(q, k, v, rel_h, rel_w, s, d ** -0.5)   # (B, H, N, d)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, s, c)
        return nn.Dense(c, dtype=self.dtype, name="proj")(o)


class Block(nn.Module):
    heads: int
    mlp_ratio: int
    window: int          # 0: a global block
    grid: int            # side of the token grid the block sees
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=LN_EPS, dtype=self.dtype, name=name)
        h = ln("norm1")(x)
        if self.window:
            win, padded = window_partition(h, self.window)
            win = Attention(self.heads, self.window, self.dtype,
                            name="attn")(win)
            h = window_unpartition(win, self.window, padded, x.shape[1:3])
        else:
            h = Attention(self.heads, self.grid, self.dtype, name="attn")(h)
        x = x + h
        h = nn.Dense(self.mlp_ratio * c, dtype=self.dtype, name="fc1")(
            ln("norm2")(x))
        h = nn.Dense(c, dtype=self.dtype, name="fc2")(
            jax.nn.gelu(h, approximate=False))
        return x + h


class PatchEmbed(nn.Module):
    """A P × P convolution of stride P with bias, computed as the matmul it
    is: rows regrouped into patches, (B, H, W, 3) or the loader's
    row-flattened (B, H, W·3) (``network.HOST_ROWS``) alike — the same
    memory — -> (B, H/P, W/P, width).  The kernel keeps a convolution's
    shape (P, P, 3, width)."""

    width: int
    patch: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, images):
        p = self.patch
        b, h = images.shape[:2]
        w = images.size // (b * h * 3)
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (p, p, 3, self.width), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.width,),
                          jnp.float32)
        x = images.astype(self.dtype).reshape(b, h // p, p, w // p, p * 3)
        x = x.transpose(0, 1, 3, 2, 4).reshape(b, h // p, w // p, p * p * 3)
        x = jnp.dot(x, kernel.reshape(-1, self.width).astype(self.dtype),
                    preferred_element_type=jnp.float32)
        return (x + bias).astype(self.dtype)


class ViT(nn.Module):
    """images (B, H, W, 3) or (B, H, W·3) -> the last block's map
    (B, H/16, W/16, C)."""

    grid: int                        # H / patch = W / patch
    patch: int = 16
    width: int = 768
    depth: int = 12
    heads: int = 12
    mlp_ratio: int = 4
    window: int = 14
    global_blocks: Tuple[int, ...] = (2, 5, 8, 11)
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, images):
        p, g = self.patch, self.grid
        assert images.shape[1] == g * p and images.size == \
            images.shape[0] * (g * p) ** 2 * 3, (
            f"this ViT holds its position vectors at a {g} x {g} grid: it "
            f"takes {g * p} x {g * p} images, not {images.shape[1:]}")
        with jax.named_scope("vit/patch_embed"):
            x = PatchEmbed(self.width, p, self.dtype,
                           name="patch_embed")(images)
            pos = self.param("pos_embed", nn.initializers.normal(0.02),
                             (1, g, g, self.width), jnp.float32)
            x = x + pos.astype(x.dtype)
        for i in range(self.depth):
            glob = i in self.global_blocks
            with jax.named_scope("vit/block_global" if glob
                                 else "vit/block_window"):
                x = Block(self.heads, self.mlp_ratio,
                          0 if glob else self.window, g, self.dtype,
                          name=f"block{i}")(x)
        return x


class SimpleFeaturePyramid(nn.Module):
    """The trunk's one stride-16 map -> P2..P6 (strides 4..64)."""

    out_channels: int = 256
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, f):
        c = f.shape[-1]
        up = lambda ch, name, x: nn.ConvTranspose(  # noqa: E731
            ch, (2, 2), strides=(2, 2), dtype=self.dtype, name=name)(x)
        ln = lambda name, x: nn.LayerNorm(  # noqa: E731
            epsilon=LN_EPS, dtype=self.dtype, name=name)(x)
        with jax.named_scope("vit/sfp"):
            x2 = up(c // 2, "p2_deconv1", f)
            x2 = jax.nn.gelu(ln("p2_norm", x2), approximate=False)
            levels = {2: up(c // 4, "p2_deconv2", x2),
                      3: up(c // 2, "p3_deconv", f),
                      4: f,
                      5: nn.max_pool(f, (2, 2), strides=(2, 2))}
            out = []
            for i, x in levels.items():
                x = nn.Conv(self.out_channels, (1, 1), use_bias=False,
                            dtype=self.dtype, name=f"lateral{i}")(x)
                x = ln(f"lateral{i}_norm", x)
                x = nn.Conv(self.out_channels, (3, 3),
                            padding=[(1, 1), (1, 1)], use_bias=False,
                            dtype=self.dtype, name=f"post{i}")(x)
                out.append(ln(f"post{i}_norm", x))
            p6 = nn.max_pool(out[-1], (1, 1), strides=(2, 2))
        return (*out, p6)
