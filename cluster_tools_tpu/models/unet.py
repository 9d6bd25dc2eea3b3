"""3-D U-Net in flax, designed for the TPU MXU.

Replaces the capability of the reference's per-job PyTorch CNNs (SURVEY.md
§2a "inference": boundary/affinity prediction over blocks with halo).
TPU-first choices:

- channels-last (NDHWC) layout — the native layout for XLA TPU convolutions,
- bfloat16 compute with float32 params (``dtype``/``param_dtype``),
- GroupNorm (batch-size independent: blocks are the batch),
- strided-conv downsampling and transpose-conv upsampling (keeps everything
  as convolutions on the MXU).

Input/output: ``(batch, z, y, x, c_in) -> (batch, z, y, x, out_channels)``,
logits (callers apply sigmoid/softmax).
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp

from .unet_gcr import UNet3DGCR


class ConvBlock(nn.Module):
    features: int
    dtype: Any = jnp.bfloat16
    norm: Any = "group"

    @nn.compact
    def __call__(self, x):
        for _ in range(2):
            x = nn.Conv(
                self.features, (3, 3, 3), padding="SAME", dtype=self.dtype
            )(x)
            if self.norm == "group":
                x = nn.GroupNorm(
                    num_groups=min(8, self.features), dtype=jnp.float32
                )(x)
            x = nn.gelu(x)
        return x


class UNet3D(nn.Module):
    """Symmetric 3-D U-Net.

    ``depth`` pooling levels halve each spatial dim; inputs must be
    divisible by ``2**depth`` per axis (the inference task pads blocks to
    meet this).
    """

    out_channels: int = 1
    base_features: int = 16
    depth: int = 2
    dtype: Any = jnp.bfloat16
    # "group" or None.  GroupNorm statistics span the whole input window, so
    # blockwise-with-halo prediction is only *approximately* equal to a
    # single-shot forward; norm=None makes the network purely convolutional
    # (exactly shift-invariant, blockwise == single-shot inside the
    # receptive field).
    norm: Any = "group"

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        skips = []
        feats = self.base_features
        for _ in range(self.depth):
            x = ConvBlock(feats, self.dtype, self.norm)(x)
            skips.append(x)
            x = nn.Conv(
                feats * 2, (2, 2, 2), strides=(2, 2, 2), dtype=self.dtype
            )(x)
            feats *= 2
        x = ConvBlock(feats, self.dtype, self.norm)(x)
        for skip in reversed(skips):
            feats //= 2
            x = nn.ConvTranspose(
                feats, (2, 2, 2), strides=(2, 2, 2), dtype=self.dtype
            )(x)
            x = jnp.concatenate([x, skip.astype(x.dtype)], axis=-1)
            x = ConvBlock(feats, self.dtype, self.norm)(x)
        x = nn.Conv(self.out_channels, (1, 1, 1), dtype=jnp.float32)(x)
        return x


_MODELS = {"unet3d": UNet3D, "unet3d_gcr": UNet3DGCR}


def get_model(name: str, **kwargs) -> nn.Module:
    try:
        cls = _MODELS[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; available: {sorted(_MODELS)}")
    return cls(**kwargs)
