"""RPN and R-CNN output heads.

Reference graph pieces (``rcnn/symbol/symbol_resnet.py`` /
``symbol_vgg.py``):

* RPN: 3×3 conv (512 ch) + relu → two sibling 1×1 convs:
  ``rpn_cls_score`` (2A ch) and ``rpn_bbox_pred`` (4A ch).
* RCNN: head body (VGG fc6/7 or ResNet stage5 pool) → two FCs:
  ``cls_score`` (K) and ``bbox_pred`` (4K).
* Mask (capability target, Mask R-CNN): 4×[3×3 conv 256] → 2× deconv →
  1×1 conv K channels, per-class 28×28 sigmoid masks.
* ``layer_norm=True`` (the ViTDet preset): the hidden convs of the box and mask
  heads lose their bias and gain a LayerNorm over the channels at each
  position (eps 1e-6) before the ReLU; the RPN gets a second hidden conv.

Channel layout note (documented divergence): MXNet lays RPN outputs as
(B, 2A, H, W) with softmax over a reshaped axis; here NHWC convs emit
(B, H, W, 2A) reshaped to (B, H·W·A, 2) so that the flattened anchor index
equals ``(y·W + x)·A + a`` — the exact order `ops.anchors.all_anchors`
emits.  The layouts are permutations of each other; the math is identical.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp


def conv_norm_relu(x, channels: int, name: str, layer_norm: bool, dtype):
    """3×3 conv + ReLU; with ``layer_norm`` the conv has no bias and a
    LayerNorm over the channels stands between the two."""
    x = nn.Conv(channels, (3, 3), padding=[(1, 1), (1, 1)],
                use_bias=not layer_norm, dtype=dtype, name=name)(x)
    if layer_norm:
        x = nn.LayerNorm(epsilon=1e-6, dtype=dtype, name=f"{name}_norm")(x)
    return nn.relu(x)


class RPNHead(nn.Module):
    num_anchors: int = 9
    channels: int = 512
    convs: int = 1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, feat):
        """feat: (B, H, W, C) → (cls_logits (B, H·W·A, 2),
        bbox_deltas (B, H·W·A, 4))."""
        a = self.num_anchors
        # reference init: Normal(0.01) for all new RPN layers
        init = nn.initializers.normal(0.01)
        x = feat
        for i in range(1, self.convs + 1):
            x = nn.relu(nn.Conv(
                self.channels, (3, 3), padding=[(1, 1), (1, 1)],
                kernel_init=init, dtype=self.dtype,
                name="rpn_conv_3x3" if i == 1 else f"rpn_conv_3x3_{i}")(x))
        cls = nn.Conv(2 * a, (1, 1), kernel_init=init, dtype=self.dtype,
                      name="rpn_cls_score")(x)
        bbox = nn.Conv(4 * a, (1, 1), kernel_init=init, dtype=self.dtype,
                       name="rpn_bbox_pred")(x)
        b, h, w, _ = cls.shape
        cls = cls.reshape(b, h * w * a, 2).astype(jnp.float32)
        bbox = bbox.reshape(b, h * w * a, 4).astype(jnp.float32)
        return cls, bbox


class RCNNOutput(nn.Module):
    """cls_score / bbox_pred FCs on the head-body embedding."""

    num_classes: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # reference init: cls_score Normal(0.01), bbox_pred Normal(0.001)
        cls = nn.Dense(self.num_classes, kernel_init=nn.initializers.normal(0.01),
                       dtype=self.dtype, name="cls_score")(x)
        bbox = nn.Dense(4 * self.num_classes, kernel_init=nn.initializers.normal(0.001),
                        dtype=self.dtype, name="bbox_pred")(x)
        return cls.astype(jnp.float32), bbox.astype(jnp.float32)


class MaskHead(nn.Module):
    """Mask R-CNN head: 4 convs + deconv ×2 + per-class 1×1 (28×28 out from
    14×14 RoI features)."""

    num_classes: int
    channels: int = 256
    layer_norm: bool = False
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        """x: (R, 14, 14, C) → (R, 28, 28, K) logits."""
        for i in range(1, 5):
            x = conv_norm_relu(x, self.channels, f"mask_conv{i}",
                               self.layer_norm, self.dtype)
        x = nn.ConvTranspose(self.channels, (2, 2), strides=(2, 2),
                             dtype=self.dtype, name="mask_deconv")(x)
        x = nn.relu(x)
        x = nn.Conv(self.num_classes, (1, 1), dtype=self.dtype, name="mask_out")(x)
        return x.astype(jnp.float32)
