"""Flax models for the inference task (boundary/affinity CNNs).

The reference's inference task loaded arbitrary PyTorch models per job
(SURVEY.md §2a "inference"); the rebuild ships a TPU-native model family —
3-D U-Nets in flax, bfloat16 compute — the repo's own ``UNet3D`` and
pytorch-3dunet's ``UNet3D`` (``unet3d_gcr``) — plus a registry so checkpoints
can name their architecture.
"""

from .unet import UNet3D, get_model
from .unet_gcr import UNet3DGCR

__all__ = ["UNet3D", "UNet3DGCR", "get_model"]
