"""pytorch-3dunet's ``UNet3D`` in flax, layer for layer.

The most widely used public 3-D U-Net for boundary prediction
(github.com/wolny/pytorch-3dunet, ``pytorch3dunet/unet3d/model.py``; Wolny
et al., eLife 2020, the PlantSeg network), at its class defaults:
``f_maps`` 64 and ``num_levels`` 4 (widths 64 / 128 / 256 / 512),
``layer_order`` ``'gcr'``, ``num_groups`` 8, conv kernel 3 with padding 1,
max pooling, nearest-neighbour upsampling joined by concatenation, a final
1x1x1 conv.  With ``f_l = f_maps * 2**l`` and ``L = num_levels``:

- ``SingleConv(a -> b)(x) = ReLU(Conv3d_3x3x3(GN(x)))``: the conv pads by 1
  and has no bias; GroupNorm runs over the *input's* ``a`` channels in
  ``num_groups`` groups (1 where ``a < num_groups``), affine, eps 1e-5.
- encoder: ``E_0 = DC_enc(x; in -> f_0)``, ``E_l = DC_enc(MaxPool_l(E_{l-1});
  f_{l-1} -> f_l)`` with ``DC_enc(a -> b) = SingleConv(m -> b) o
  SingleConv(a -> m)``, ``m = max(b // 2, a)``.
- decoder, ``l = L-2 .. 0``: ``D_{L-1} = E_{L-1}``, ``D_l =
  DC_dec(concat[E_l, Up_l(D_{l+1})]; f_l + f_{l+1} -> f_l)`` with
  ``DC_dec(a -> b) = SingleConv(b -> b) o SingleConv(a -> b)``; ``Up_l``
  repeats each voxel by level ``l``'s pool factor (nearest neighbour to
  ``E_l``'s size: the extents are whole multiples), encoder features first.
- head: ``Conv3d_1x1x1(D_0) + bias``, logits (the inference task applies
  the sigmoid, as the source does in eval mode).

Precision: every conv takes bfloat16 operands and accumulates and returns
float32; GroupNorm statistics, activations and params are float32.  Each
level runs under ``jax.named_scope`` ``unet.enc{l}`` / ``unet.dec{l}`` and
the head under ``unet.head`` (docs/OBSERVABILITY.md), so that a device trace
attributes the forward by level.

Input/output: ``(batch, z, y, x, c_in) -> (batch, z, y, x, out_channels)``;
every extent must be a multiple of :meth:`UNet3DGCR.input_multiple` (the
inference task pads its blocks to it).  The parameter tree follows the
source's module names (``enc{l}/SingleConv{1,2}/{groupnorm,conv}``,
``dec{l}/...``, ``final_conv``), which is what ``torch_import`` maps a
pytorch-3dunet checkpoint onto.
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

#: torch.nn.GroupNorm's default (flax's is 1e-6)
GN_EPS = 1e-5


def _operand(a):
    """A conv operand as the network takes it: rounded to bfloat16."""
    return a.astype(jnp.bfloat16)


def _groups(channels: int, num_groups: int) -> int:
    # pytorch-3dunet's create_conv: fewer channels than groups -> one group
    return 1 if channels < num_groups else num_groups


class GroupNorm(nn.Module):
    """``torch.nn.GroupNorm`` over the last axis, statistics in float32 over
    every axis but the batch's (two passes: mean, then the centred second
    moment); the result as a conv operand."""

    num_groups: int

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        g = self.num_groups
        xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (g, c // g))
        axes = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
        mean = xg.mean(axes, keepdims=True)
        var = jnp.square(xg - mean).mean(axes, keepdims=True)
        y = ((xg - mean) * lax.rsqrt(var + GN_EPS)).reshape(x.shape)
        # what follows is a conv, which takes its operand so: rounding here
        # keeps the normalised window at the operand's width in memory
        return _operand(y * scale + bias)


class Conv(nn.Module):
    """``torch.nn.Conv3d`` with padding ``kernel_size // 2``, channels last:
    bfloat16 operands, float32 accumulation and result."""

    features: int
    kernel_size: int = 3
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        k = self.kernel_size
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (k, k, k, x.shape[-1], self.features), jnp.float32,
        )
        y = lax.conv_general_dilated(
            _operand(x), _operand(kernel), (1, 1, 1), [(k // 2, k // 2)] * 3,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
            preferred_element_type=jnp.float32,
        )
        if self.use_bias:
            y = y + self.param(
                "bias", nn.initializers.zeros, (self.features,), jnp.float32
            )
        return y


class SingleConv(nn.Module):
    """Order ``'gcr'``: GroupNorm over the input, conv, ReLU."""

    features: int
    num_groups: int

    @nn.compact
    def __call__(self, x):
        x = GroupNorm(_groups(x.shape[-1], self.num_groups), name="groupnorm")(x)
        return nn.relu(Conv(self.features, name="conv")(x))


class DoubleConv(nn.Module):
    """Two ``SingleConv`` of widths ``features`` = (first, second)."""

    features: Tuple[int, int]
    num_groups: int

    @nn.compact
    def __call__(self, x):
        for k, feats in enumerate(self.features, start=1):
            x = SingleConv(feats, self.num_groups, name=f"SingleConv{k}")(x)
        return x


def _triple(p) -> Tuple[int, int, int]:
    return (int(p),) * 3 if isinstance(p, int) else tuple(int(v) for v in p)


class UNet3DGCR(nn.Module):
    """pytorch-3dunet's ``UNet3D`` (module docstring).  ``pool_kernel_size``
    is the source's: an int or one per-axis triple, used at every level."""

    out_channels: int = 1
    f_maps: int = 64
    num_levels: int = 4
    num_groups: int = 8
    pool_kernel_size: Any = 2

    def __post_init__(self):
        # a configuration read from JSON gives lists; a module is hashed
        object.__setattr__(self, "pool_kernel_size", _triple(self.pool_kernel_size))
        super().__post_init__()

    def pool_factors(self) -> List[Tuple[int, int, int]]:
        """One per-axis factor per pooling, deepest last."""
        return [self.pool_kernel_size] * (self.num_levels - 1)

    def input_multiple(self) -> Tuple[int, int, int]:
        """What every input extent must be a multiple of, per axis."""
        mult = [1, 1, 1]
        for f in self.pool_factors():
            mult = [m * v for m, v in zip(mult, f)]
        return tuple(mult)

    def widths(self, in_channels: int = 1) -> List[Tuple[int, int, int, int]]:
        """``(level, c_in, c_out, kernel size)`` of every conv, in order (the
        head at level 0)."""
        feats = [self.f_maps * 2 ** l for l in range(self.num_levels)]
        convs, a = [], in_channels
        for l, b in enumerate(feats):
            m = max(b // 2, a)
            convs += [(l, a, m, 3), (l, m, b, 3)]
            a = b
        for l in reversed(range(self.num_levels - 1)):
            convs += [(l, feats[l] + feats[l + 1], feats[l], 3), (l, feats[l], feats[l], 3)]
        return convs + [(0, feats[0], self.out_channels, 1)]

    def forward_flops(self, shape: Sequence[int], in_channels: int = 1) -> int:
        """Multiply-adds of the convs (2 FLOPs each) of one forward over an
        input of spatial extent ``shape``."""
        voxels = [int(shape[0] * shape[1] * shape[2])]
        for f in self.pool_factors():
            voxels.append(voxels[-1] // (f[0] * f[1] * f[2]))
        return sum(2 * k ** 3 * a * b * voxels[l] for l, a, b, k in self.widths(in_channels))

    @nn.compact
    def __call__(self, x):
        feats = [self.f_maps * 2 ** l for l in range(self.num_levels)]
        pools = self.pool_factors()
        skips = []
        for l, b in enumerate(feats):
            with jax.named_scope(f"unet.enc{l}"):
                if l:
                    x = nn.max_pool(x, pools[l - 1], strides=pools[l - 1])
                m = max(b // 2, x.shape[-1])
                x = DoubleConv((m, b), self.num_groups, name=f"enc{l}")(x)
            skips.append(x)
        for l in reversed(range(self.num_levels - 1)):
            with jax.named_scope(f"unet.dec{l}"):
                for axis, f in enumerate(pools[l], start=1):
                    x = jnp.repeat(x, f, axis=axis)
                x = jnp.concatenate([skips[l], x], axis=-1)
                x = DoubleConv((feats[l], feats[l]), self.num_groups, name=f"dec{l}")(x)
        with jax.named_scope("unet.head"):
            return Conv(self.out_channels, 1, use_bias=True, name="final_conv")(x)
