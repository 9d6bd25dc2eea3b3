"""The plain reference of the ``unet_pred`` comparison: pytorch-3dunet's
``UNet3D`` forward in float32, from its layer equations, and the counts of
the convs' FLOPs.  Imports nothing of the program.

The network (github.com/wolny/pytorch-3dunet ``unet3d/model.py::UNet3D``,
``layer_order`` ``'gcr'``), with ``f_l = f_maps * 2**l``, ``L = num_levels``:

- ``SingleConv(a -> b)(x) = ReLU(Conv3d_3x3x3, pad 1, no bias(GN(x)))``,
  GroupNorm over the input's ``a`` channels in ``num_groups`` groups (1
  where ``a < num_groups``), affine, eps 1e-5 (``torch.nn.GroupNorm``);
- ``E_0 = DC_enc(x; in -> f_0)``, ``E_l = DC_enc(MaxPool_l(E_{l-1}); f_{l-1}
  -> f_l)``, ``DC_enc(a -> b) = SingleConv(m -> b) o SingleConv(a -> m)``,
  ``m = max(b // 2, a)``;
- ``D_{L-1} = E_{L-1}``, ``D_l = DC_dec(concat[E_l, Up_l(D_{l+1})]; f_l +
  f_{l+1} -> f_l)`` for ``l = L-2 .. 0``, ``DC_dec(a -> b) = SingleConv(b ->
  b) o SingleConv(a -> b)``, ``Up_l`` nearest neighbour to ``E_l``'s size;
- logits ``= Conv3d_1x1x1(D_0) + bias``.

Computed with numpy and ``lax.conv_general_dilated`` in float32 under
``jax.default_matmul_precision("highest")`` (every conv at
``Precision.HIGHEST`` too).  Departures from a literal forward, none of which
changes the mathematics:

- each conv is computed in z-slabs of its output (the input planes one
  beyond, zero outside the window, as the conv's own padding), so that a
  whole 40 x 320 x 512 window fits one chip beside its activations;
- every GroupNorm's statistics are taken over the whole window first, from
  the float64 sums of each channel and of its square (each slab's float32
  sums, added in float64; the variance as the second moment less the
  squared mean);
- the decoder's concatenation is never built whole: a slab's input is the
  encoder part's planes beside the upsampled planes of the deeper part, and
  a group's moments are the mean of its channels' moments (a group may
  straddle the two parts).  Whole-factor nearest upsampling repeats every
  value equally often, so the upsampled part's moments are those of the
  deeper array itself, which is what is summed;
- the extents are whole multiples of the pool factors (the task pads its
  blocks so), so ``Up_l`` is a repeat and ``MaxPool_l`` drops nothing.

The input is the unit as the kernel saw it: the outer block (block + halo,
clipped by the stack), normalised as the task normalises it (``(x - lo) /
max(hi - lo, 1e-6)`` with the configuration's ``normalize_range``) and
padded at the far side with 0 to the outer shape (the task's
``pad_block_to``).  The weights are the ones a job recorded
(``<job tmp>/inference.*.weights.npz``, flat ``/``-joined keys), or ones
drawn here from the seed (:func:`draw_weights`) and handed to a job as its
checkpoint in the same flat layout.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np

EPS = 1e-5
#: output planes a slab computes (a level-0 slab of the decoder's first
#: conv holds 6 planes of 192 channels and their highest-precision splits)
SLAB = 4


# --------------------------------------------------------------------------
# geometry and FLOPs, from the configuration alone
# --------------------------------------------------------------------------


def pool_factors(model: dict) -> List[Tuple[int, int, int]]:
    """One per-axis factor per pooling (``num_levels - 1``): the source's
    ``pool_kernel_size``, an int or one (z, y, x) triple, at every level."""
    p = model.get("pool_kernel_size", 2)
    factor = (int(p),) * 3 if isinstance(p, int) else tuple(int(v) for v in p)
    return [factor] * (int(model["num_levels"]) - 1)


def conv_widths(model: dict, in_channels: int = 1) -> List[Tuple[int, int, int, int]]:
    """``(level, c_in, c_out, kernel)`` of every conv (the head at level 0)."""
    feats = [int(model["f_maps"]) * 2 ** l for l in range(int(model["num_levels"]))]
    out, a = [], in_channels
    for l, b in enumerate(feats):
        m = max(b // 2, a)
        out += [(l, a, m, 3), (l, m, b, 3)]
        a = b
    for l in reversed(range(len(feats) - 1)):
        out += [(l, feats[l] + feats[l + 1], feats[l], 3), (l, feats[l], feats[l], 3)]
    return out + [(0, feats[0], int(model.get("out_channels", 1)), 1)]


def forward_flops(model: dict, shape: Sequence[int], in_channels: int = 1) -> int:
    """FLOPs of the convs of one forward over an input of spatial extent
    ``shape``: 2 per multiply-add, k^3 * c_in * c_out a voxel of the conv's
    level.  GroupNorm, pooling, upsampling and the sigmoid are not counted."""
    voxels = [int(np.prod(shape))]
    for f in pool_factors(model):
        voxels.append(voxels[-1] // int(np.prod(f)))
    return sum(2 * k ** 3 * a * b * voxels[l] for l, a, b, k in conv_widths(model, in_channels))


def draw_weights(model: dict, rng: np.random.Generator,
                 in_channels: int = 1) -> Dict[str, np.ndarray]:
    """Every leaf of the network, float32, drawn from ``rng``, under the
    flat names a job records (``"enc0/SingleConv1/conv/kernel"``, ...,
    ``"final_conv/bias"``).  Conv kernels N(0, 1 / fan_in), the scale of
    flax's ``lecun_normal``; no leaf is left at an init's trivial value:
    every GroupNorm's scale in [0.5, 1.5] and bias in [-0.5, 0.5], the
    head's bias 1 to 2 away from 0, either sign.  A forward that drops an
    affine or the head's bias is then off by more than a logit of 0.5."""
    levels = int(model["num_levels"])
    names = ([f"enc{l}/SingleConv{k}" for l in range(levels) for k in (1, 2)]
             + [f"dec{l}/SingleConv{k}" for l in reversed(range(levels - 1)) for k in (1, 2)])
    convs = conv_widths(model, in_channels)
    w: Dict[str, np.ndarray] = {}

    def kernel(k, a, b):
        return rng.standard_normal((k, k, k, a, b), np.float32) * np.float32(
            1.0 / np.sqrt(k ** 3 * a))

    for name, (_, a, b, k) in zip(names, convs[:-1]):
        w[name + "/groupnorm/scale"] = rng.uniform(0.5, 1.5, a).astype(np.float32)
        w[name + "/groupnorm/bias"] = rng.uniform(-0.5, 0.5, a).astype(np.float32)
        w[name + "/conv/kernel"] = kernel(k, a, b)
    _, a, b, k = convs[-1]
    w["final_conv/kernel"] = kernel(k, a, b)
    w["final_conv/bias"] = (rng.choice([-1.0, 1.0], b) * rng.uniform(1.0, 2.0, b)
                            ).astype(np.float32)
    return w


def write_weights(path: str, w: Dict[str, np.ndarray]) -> None:
    """``w`` as a flat npz, ``params/``-prefixed ``/``-joined keys: the
    layout a job records and reads back as ``checkpoint_path``."""
    np.savez(path, **{"params/" + k: v for k, v in w.items()})


# --------------------------------------------------------------------------
# what the job stored and recorded
# --------------------------------------------------------------------------


def read_zarr_nd(path: str, key: str) -> Tuple[np.ndarray, np.ndarray]:
    """A whole zarr v2 array of any rank from its files, and where its
    chunks were never written (``missing``, True there)."""
    root = os.path.join(path, key)
    with open(os.path.join(root, ".zarray")) as f:
        meta = json.load(f)
    if meta.get("order", "C") != "C" or meta.get("filters"):
        raise ValueError(f"{root}: only C-order, unfiltered zarr v2 is read here")
    shape, chunks = tuple(meta["shape"]), tuple(meta["chunks"])
    dtype, comp = np.dtype(meta["dtype"]), meta.get("compressor")
    if comp is not None and comp.get("id") not in ("gzip", "zlib"):
        raise ValueError(f"{root}: compressor {comp} is not read here")
    sep = meta.get("dimension_separator", ".")
    out = np.full(shape, meta.get("fill_value") or 0, dtype)
    missing = np.zeros(shape, bool)
    grid = [range(-(-s // c)) for s, c in zip(shape, chunks)]

    def one(idx):
        where = tuple(slice(i * c, min((i + 1) * c, s))
                      for i, c, s in zip(idx, chunks, shape))
        try:
            with open(os.path.join(root, sep.join(map(str, idx))), "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            missing[where] = True
            return
        if comp is not None:
            raw = zlib.decompress(raw, 47)  # gzip or zlib header
        chunk = np.frombuffer(raw, dtype).reshape(chunks)
        out[where] = chunk[tuple(slice(0, w.stop - w.start) for w in where)]

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(one, np.ndindex(*(len(g) for g in grid))))
    return out, missing


def recorded_weights(tmp: str) -> Dict[str, np.ndarray]:
    """The weights a job recorded in its tmp folder: ``"enc0/SingleConv1/
    conv/kernel"`` -> array (the leading ``params/`` dropped)."""
    found = sorted(glob.glob(os.path.join(tmp, "inference.*.weights.npz")))
    if len(found) != 1:
        raise FileNotFoundError(f"{tmp}: {len(found)} recorded weight files")
    with np.load(found[0]) as f:
        return {k.split("/", 1)[1] if k.startswith("params/") else k: f[k]
                for k in f.files}


def unit_input(vol: np.ndarray, olo, ohi, outer, normalize_range) -> np.ndarray:
    """The unit as the kernel saw it: the clipped outer block, normalised as
    the task does, padded at the far side with 0 to ``outer``."""
    lo, hi = float(normalize_range[0]), float(normalize_range[1])
    data = vol[tuple(slice(a, b) for a, b in zip(olo, ohi))].astype(np.float32)
    data = (data - lo) / max(hi - lo, 1e-6)
    return np.pad(data, [(0, o - s) for s, o in zip(data.shape, outer)])


# --------------------------------------------------------------------------
# the forward
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs():
    """The device programs.  An activation is held as ``(z, y, x * c)``
    float32: with the channels folded into the last axis nothing of it is
    layout padding on a TPU (64 channels would fill half of a 128-lane
    tile)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def channel_moments(y, keep):
        y = y * keep[:, None, None, None]
        return y.sum((0, 1, 2)), jnp.square(y).sum((0, 1, 2))

    @functools.partial(jax.jit, static_argnames=("shapes", "factors", "slab"))
    def conv_slab(parts, z0, fresh, mu, inv, gamma, beta, kernel, *, shapes, factors, slab):
        """``slab`` output planes from ``z0`` of ReLU(conv(GN(concat(parts)))),
        folded, with the channel sums and sums of squares of the planes from
        ``fresh`` on."""
        depth = parts[0].shape[0]
        zf = z0 - 1 + jnp.arange(slab + 2)
        valid = (zf >= 0) & (zf < depth)
        pieces = []
        for p, (ny, nx, c), (fz, fy, fx) in zip(parts, shapes, factors):
            x = jnp.take(p, jnp.clip(zf, 0, depth - 1) // fz, axis=0).reshape(
                slab + 2, ny, nx, c)
            pieces.append(jnp.repeat(jnp.repeat(x, fy, axis=1), fx, axis=2))
        x = jnp.concatenate(pieces, axis=-1)
        x = (x - mu) * inv * gamma + beta
        x = jnp.where(valid[:, None, None, None], x, 0.0)   # the conv's own zero padding
        y = lax.conv_general_dilated(
            x[None], kernel, (1, 1, 1), [(0, 0), (1, 1), (1, 1)],
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
            precision=lax.Precision.HIGHEST)[0]
        y = jnp.maximum(y, 0.0)
        sums, squares = channel_moments(y, (jnp.arange(slab) >= fresh).astype(y.dtype))
        return y.reshape(slab, y.shape[1], -1), sums, squares

    @functools.partial(jax.jit, static_argnames=("shape", "factor"))
    def max_pool(x, *, shape, factor):
        x = x.reshape(x.shape[:2] + shape)
        y = lax.reduce_window(x, -jnp.inf, lax.max, factor + (1,), factor + (1,), "VALID")
        sums, squares = channel_moments(y, jnp.ones(y.shape[0], y.dtype))
        return y.reshape(y.shape[0], y.shape[1], -1), sums, squares

    @functools.partial(jax.jit, static_argnames=("shape",))
    def head(x, kernel, bias, *, shape):
        x = x.reshape(x.shape[:2] + shape)
        return jnp.einsum("zyxc,co->ozyx", x, kernel[0, 0, 0],
                          precision=lax.Precision.HIGHEST) + bias[:, None, None, None]

    return conv_slab, max_pool, head


class Act:
    """An activation on the default device, folded to ``(z, y, x * c)``
    float32, with the float64 sums over the window of every channel and of
    its square: what a GroupNorm over it needs."""

    def __init__(self, x, shape, sums, squares):
        self.x, self.shape = x, tuple(shape)   # shape: (z, y, x, c)
        self.sums = np.asarray(sums, np.float64)
        self.squares = np.asarray(squares, np.float64)

    @property
    def n(self) -> int:
        return int(np.prod(self.shape[:3]))


def single_conv(parts: List[Act], factors, w: Dict[str, np.ndarray], prefix: str,
                num_groups: int) -> Act:
    """``SingleConv`` on the concatenation of ``parts`` along channels
    (``factors[i]`` the repeat that takes part ``i`` to the first's extent),
    computed in z-slabs of its output."""
    import jax.numpy as jnp

    conv_slab = _programs()[0]
    c = sum(p.shape[3] for p in parts)
    groups = 1 if c < num_groups else num_groups
    per = c // groups
    # every channel of the concatenation has the window's voxels, a repeated
    # part each of its values equally often: a group's moments are the mean
    # of its channels' moments, each over its own array (float64)
    mean = np.concatenate([p.sums / p.n for p in parts]).reshape(groups, per).mean(1)
    second = np.concatenate([p.squares / p.n for p in parts]).reshape(groups, per).mean(1)
    mu = np.repeat(mean, per).astype(np.float32)
    inv = np.repeat(1.0 / np.sqrt(second - mean ** 2 + EPS), per).astype(np.float32)
    kernel = w[prefix + "/conv/kernel"].astype(np.float32)
    depth, ny, nx = parts[0].shape[:3]
    slab = min(SLAB, depth)
    out, sums, squares = [], 0.0, 0.0
    for z0 in range(0, depth, slab):
        start = min(z0, depth - slab)   # the last slab overlaps: its fresh planes count
        y, s1, s2 = conv_slab(
            tuple(p.x for p in parts), start, z0 - start, mu, inv,
            w[prefix + "/groupnorm/scale"], w[prefix + "/groupnorm/bias"], kernel,
            shapes=tuple(p.shape[1:] for p in parts),
            factors=tuple(tuple(f) for f in factors), slab=slab)
        out.append(y[z0 - start:])
        sums, squares = sums + np.asarray(s1, np.float64), squares + np.asarray(s2, np.float64)
    return Act(jnp.concatenate(out, axis=0), (depth, ny, nx, kernel.shape[-1]),
               sums, squares)


def forward_logits(x: np.ndarray, w: Dict[str, np.ndarray], model: dict) -> np.ndarray:
    """Logits ``(out_channels, z, y, x)`` of the network on one window ``x``
    (z, y, x), float32 throughout, on the default device."""
    import jax
    import jax.numpy as jnp

    _, max_pool, head = _programs()
    groups, levels = int(model.get("num_groups", 8)), int(model["num_levels"])
    pools = pool_factors(model)
    x = np.asarray(x, np.float32)
    with jax.default_matmul_precision("highest"):
        h = Act(jnp.asarray(x), x.shape + (1,), [x.sum(dtype=np.float64)],
                [np.square(x, dtype=np.float64).sum()])
        skips = []
        for l in range(levels):
            if l:
                f = pools[l - 1]
                y, s1, s2 = max_pool(h.x, shape=h.shape[2:], factor=f)
                h = Act(y, [n // k for n, k in zip(h.shape[:3], f)] + [h.shape[3]], s1, s2)
            for k in (1, 2):
                h = single_conv([h], [(1, 1, 1)], w, f"enc{l}/SingleConv{k}", groups)
            skips.append(h)
        for l in reversed(range(levels - 1)):
            h = single_conv([skips[l], h], [(1, 1, 1), pools[l]], w,
                            f"dec{l}/SingleConv1", groups)
            skips[l + 1] = None
            h = single_conv([h], [(1, 1, 1)], w, f"dec{l}/SingleConv2", groups)
        return np.asarray(head(h.x, w["final_conv/kernel"], w["final_conv/bias"],
                               shape=h.shape[2:]))
