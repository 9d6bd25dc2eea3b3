"""Import PyTorch checkpoints into the flax model zoo.

The reference's inference task executes user-supplied *PyTorch* models
(SURVEY.md §2a "inference", §2b "PyTorch (+CUDA)"); a user switching to this
framework arrives with torch-trained weights.  This module converts a torch
``state_dict`` whose architecture mirrors one of our flax models (same
layers in the same order — the "I trained the same U-Net in torch" case)
into the flax parameter tree, so the TPU inference path runs the trained
network directly.

Matching is positional: both frameworks register parameters in module
application/definition order, so the flattened torch tensors are converted
one-for-one onto the flattened flax leaves, with layout rules per kind:

- ``Conv3d.weight``      (O, I, kD, kH, kW) -> kernel (kD, kH, kW, I, O)
- ``ConvTranspose3d.weight`` (I, O, kD, kH, kW) -> kernel
  (kD, kH, kW, I, O), spatial axes FLIPPED (torch's transposed conv is the
  gradient of a correlation; ``lax.conv_transpose`` does not mirror —
  verified numerically in ``tests/test_inference.py``)
- ``GroupNorm.weight``/``.bias`` -> ``scale``/``bias``
- ``Conv*.bias`` -> ``bias``

A shape/kind mismatch raises with the full remaining-leaf diff rather than
producing silently-wrong weights.

pytorch-3dunet checkpoints (``pytorch3dunet.unet3d.model.UNet3D``, the
flax twin :class:`~.unet_gcr.UNet3DGCR`) are matched by name instead
(:func:`pytorch_3dunet_to_flax`): ``encoders.{l}.basic_module.SingleConv{k}
.{groupnorm,conv}.*`` -> ``enc{l}/SingleConv{k}/...``, ``decoders.{i}...``
-> ``dec{L-2-i}/...`` (the source lists its decoders deepest first), and
``final_conv.*``.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np


def _convert_leaf(path, flax_leaf, torch_key: str, tensor: np.ndarray):
    """Convert one torch tensor to the layout of one flax leaf, or raise."""
    kind = path[-1]
    want = tuple(flax_leaf.shape)
    if kind == "kernel" and tensor.ndim == 5:
        if "ConvTranspose" in path[-2]:
            # (I, O, kD, kH, kW) -> (kD, kH, kW, I, O), mirrored spatially
            conv = np.ascontiguousarray(
                tensor.transpose(2, 3, 4, 0, 1)[::-1, ::-1, ::-1]
            )
        else:
            # (O, I, kD, kH, kW) -> (kD, kH, kW, I, O)
            conv = tensor.transpose(2, 3, 4, 1, 0)
        if conv.shape != want:
            raise ValueError(
                f"flax {'/'.join(path)} wants {want}, torch {torch_key!r} "
                f"converts to {conv.shape}"
            )
        return conv
    if kind in ("scale", "bias") and tensor.ndim == 1:
        if tuple(tensor.shape) != want:
            raise ValueError(
                f"flax {'/'.join(path)} wants {want}, torch {torch_key!r} "
                f"has {tuple(tensor.shape)}"
            )
        return tensor
    raise ValueError(
        f"cannot map torch {torch_key!r} (shape {tuple(tensor.shape)}) onto "
        f"flax {'/'.join(path)} (shape {want})"
    )


def _to_array(v) -> np.ndarray:
    # .detach() first: state_dicts saved with keep_vars=True (or from
    # named_parameters()) hold requires_grad tensors that np.asarray
    # refuses to convert directly
    return np.asarray(v.detach().cpu().numpy() if hasattr(v, "detach") else v)


def infer_unet_config(state_dict: Mapping[str, Any]) -> Dict[str, Any]:
    """Deduce the UNet3D hyperparameters from a torch ``state_dict`` alone.

    A user arriving with their own torch-trained U-Net should not have to
    reverse-engineer ``base_features``/``depth``/``norm`` by hand
    (SURVEY.md §2a inference: the reference loads an arbitrary user model
    per job).  The U-Net family's tensor census is rigid enough to invert:

    - 5-D conv tensors: per level a 2-conv block + 1 downsample, a 2-conv
      bottom, per level 1 transpose + a 2-conv block, and 1 output head
      = ``6 * depth + 3``  ->  depth.
    - first conv weight ``(O, I, k, k, k)``: O = base_features,
      I = in_channels; last conv weight: O = out_channels.
    - 1-D tensors: one bias per conv without norm (``6 depth + 3``), plus a
      GroupNorm scale+bias pair per block conv (``+ 4 (2 depth + 1)``)
      with norm.

    Returns kwargs for :class:`~.unet.UNet3D` (plus ``in_channels``, which
    flax infers from the input and the caller uses for the sample shape).
    Raises ``ValueError`` naming the offending tensor when the census does
    not fit the family.
    """
    items = [
        (k, a)
        for k, v in state_dict.items()
        if "num_batches_tracked" not in k
        for a in (_to_array(v),)
    ]
    conv5 = [(k, a) for k, a in items if a.ndim == 5]
    one_d = [(k, a) for k, a in items if a.ndim == 1]
    other = [
        (k, a) for k, a in items if a.ndim not in (1, 5) and a.ndim >= 1
    ]
    if other:
        k, a = other[0]
        raise ValueError(
            f"state_dict tensor {k!r} has shape {tuple(a.shape)} — not part "
            "of the 3-D U-Net family (expected 5-D conv kernels and 1-D "
            "bias/norm vectors)"
        )
    if not conv5:
        raise ValueError(
            "state_dict holds no 5-D tensors — not a 3-D conv net"
        )
    n5 = len(conv5)
    if n5 < 3 or (n5 - 3) % 6:
        raise ValueError(
            f"{n5} conv tensors does not fit the U-Net census 6*depth + 3 "
            f"(first conv tensor: {conv5[0][0]!r})"
        )
    depth = (n5 - 3) // 6
    base_features = int(conv5[0][1].shape[0])
    in_channels = int(conv5[0][1].shape[1])
    out_channels = int(conv5[-1][1].shape[0])
    n1 = len(one_d)
    if n1 == n5:
        norm = None
    elif n1 == n5 + 4 * (2 * depth + 1):
        norm = "group"
    else:
        raise ValueError(
            f"{n1} 1-D tensors fits neither norm=None ({n5}) nor "
            f"norm='group' ({n5 + 4 * (2 * depth + 1)}) for depth={depth} "
            f"(first 1-D tensor: {one_d[0][0] if one_d else None!r})"
        )
    return {
        "out_channels": out_channels,
        "base_features": base_features,
        "depth": depth,
        "norm": norm,
        "in_channels": in_channels,
    }


def import_torch_unet(path_or_state_dict, **overrides):
    """Torch U-Net checkpoint -> ``(flax_model, variables)``, config-free.

    Infers the architecture with :func:`infer_unet_config`, instantiates
    the flax :class:`~.unet.UNet3D` twin, and converts the weights.  This
    is the "bring your own trained U-Net" entry point; for a state_dict
    that does NOT mirror the family, the census error (or the first
    unmappable tensor from the positional converter) says which tensor
    broke the match.  ``overrides`` go to the UNet3D constructor (e.g.
    ``dtype=jnp.float32`` for bit-closer parity checks).

    Caveat the shapes cannot encode: GroupNorm *group counts*.  The twin
    uses ``min(8, channels)`` groups; a checkpoint trained with a
    different grouping imports cleanly but normalizes differently —
    validate imported models against a reference forward pass.
    """
    import os

    if isinstance(path_or_state_dict, (str, bytes, os.PathLike)):
        import torch

        obj = torch.load(
            path_or_state_dict, map_location="cpu", weights_only=True
        )
        obj = _unwrap_state_dict(obj, path_or_state_dict)
    else:
        obj = path_or_state_dict
    cfg = infer_unet_config(obj)
    in_channels = cfg.pop("in_channels")
    cfg.update(overrides)
    from .unet import UNet3D

    model = UNet3D(**cfg)
    mult = 2 ** cfg["depth"]
    sample = (1, mult, mult, mult, in_channels)
    return model, torch_state_dict_to_flax(obj, model, sample)


def torch_state_dict_to_flax(
    state_dict: Mapping[str, Any], model, sample_shape
) -> Dict:
    """Convert a torch ``state_dict`` to ``model``'s flax variables.

    ``model`` is a flax module (e.g. :class:`~.unet.UNet3D`); ``sample_shape``
    an input shape used to initialize the parameter template.  The torch
    architecture must mirror the flax one layer-for-layer in order.
    """
    import flax.traverse_util as tu

    template = model.init(
        jax.random.PRNGKey(0), jnp.zeros(sample_shape, jnp.float32)
    )
    # flatten_dict preserves dict insertion order == module-application
    # order, the property positional matching relies on (same machinery as
    # tasks/inference.py's npz checkpoints)
    flax_leaves = list(tu.flatten_dict(template["params"]).items())
    torch_items = [
        (k, arr)
        for k, v in state_dict.items()
        if "num_batches_tracked" not in k
        for arr in (_to_array(v),)
        if arr.ndim >= 1
    ]
    if len(torch_items) != len(flax_leaves):
        # name the FIRST pair that fails to convert — that is where the
        # architectures diverge; the full lists follow for context
        first = None
        for (path, leaf), (tkey, tensor) in zip(flax_leaves, torch_items):
            try:
                _convert_leaf(path, leaf, tkey, tensor)
            except ValueError as e:
                first = str(e)
                break
        fpaths = ["/".join(p) for p, _ in flax_leaves]
        tkeys = [k for k, _ in torch_items]
        raise ValueError(
            f"parameter count mismatch: flax has {len(flax_leaves)} leaves, "
            f"torch has {len(torch_items)} tensors.\nfirst unmappable "
            f"tensor: {first or 'lists agree up to the shorter length'}\n"
            f"flax: {fpaths}\ntorch: {tkeys}"
        )
    flat = {}
    for (path, leaf), (tkey, tensor) in zip(flax_leaves, torch_items):
        flat[("params",) + path] = jnp.asarray(
            _convert_leaf(path, leaf, tkey, tensor), dtype=leaf.dtype
        )
    return tu.unflatten_dict(flat)


def pytorch_3dunet_to_flax(state_dict: Mapping[str, Any], model) -> Dict:
    """A pytorch-3dunet ``UNet3D`` ``state_dict`` -> ``model``'s flax
    variables (``model`` a :class:`~.unet_gcr.UNet3DGCR` of the same
    widths), matched by name.  A tensor the model does not hold, one it
    holds that the checkpoint lacks, or a shape that does not convert is
    refused, naming the tensor."""
    import flax.traverse_util as tu

    sample = (1,) + tuple(model.input_multiple()) + (1,)
    template = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros(sample, jnp.float32)
    )
    levels = int(model.num_levels)

    def torch_key(path):
        head, *inner, leaf = path
        if head.startswith("dec"):   # the source lists its decoders deepest first
            head = f"decoders.{levels - 2 - int(head[3:])}.basic_module"
        elif head.startswith("enc"):
            head = f"encoders.{int(head[3:])}.basic_module"
        leaf = {"kernel": "weight", "scale": "weight", "bias": "bias"}[leaf]
        return ".".join([head, *inner, leaf])

    leaves = tu.flatten_dict(template["params"])
    wanted = {torch_key(p): p for p in leaves}
    given = {k: v for k, v in state_dict.items() if "num_batches_tracked" not in k}
    extra = sorted(set(given) - set(wanted))
    missing = sorted(set(wanted) - set(given))
    if extra or missing:
        raise ValueError(
            "not a pytorch-3dunet UNet3D of these widths: "
            + (f"tensor {extra[0]!r} has no place in the model" if extra
               else f"tensor {missing[0]!r} is missing from the checkpoint")
            + f" ({len(extra)} extra, {len(missing)} missing)"
        )
    flat = {}
    for key, tensor in given.items():   # the checkpoint's order: first misfit named
        path = wanted[key]
        flat[("params",) + path] = jnp.asarray(
            _convert_leaf(path, leaves[path], key, _to_array(tensor)),
            dtype=jnp.float32,
        )
    return tu.unflatten_dict(flat)


def load_torch_checkpoint(path: str, model, sample_shape) -> Dict:
    """Load a ``.pt``/``.pth`` torch checkpoint file into flax variables.

    Accepts a raw ``state_dict`` or the common wrapper dicts
    (``{"state_dict": ...}`` / ``{"model_state_dict": ...}``); a
    :class:`~.unet_gcr.UNet3DGCR` is matched by name
    (:func:`pytorch_3dunet_to_flax`), every other model by position.
    """
    import torch

    from .unet_gcr import UNet3DGCR

    obj = torch.load(path, map_location="cpu", weights_only=True)
    obj = _unwrap_state_dict(obj, path)
    if isinstance(model, UNet3DGCR):
        return pytorch_3dunet_to_flax(obj, model)
    return torch_state_dict_to_flax(obj, model, sample_shape)


def _unwrap_state_dict(obj, origin):
    for key in ("state_dict", "model_state_dict", "model"):
        if isinstance(obj, dict) and key in obj and isinstance(obj[key], dict):
            obj = obj[key]
            break
    if not isinstance(obj, dict):
        raise ValueError(
            f"{origin!r} does not contain a state_dict "
            f"(got {type(obj).__name__})"
        )
    return obj
