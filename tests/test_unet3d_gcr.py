"""pytorch-3dunet's UNet3D (``models/unet_gcr.py``) against the plain
reference (``benchmark/reference_unet.py``) and a torch twin, alone and
through the inference workflow, at small sizes on the CPU."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cluster_tools_tpu.models import UNet3DGCR, get_model, unet_gcr
from cluster_tools_tpu.runtime.task import build
from cluster_tools_tpu.tasks.inference import init_params, save_checkpoint
from cluster_tools_tpu.utils.volume_utils import file_reader

from benchmark import reference_unet
from benchmark.comparisons import unet_pred

SMALL = {"f_maps": 8, "num_levels": 3, "num_groups": 8,
         "pool_kernel_size": [1, 2, 2], "out_channels": 1}


def float32_operands(monkeypatch):
    monkeypatch.setattr(unet_gcr, "_operand", lambda a: a.astype(jnp.float32))


def flat_weights(variables):
    import flax.traverse_util as tu

    return {"/".join(k[1:]): np.asarray(v) for k, v in tu.flatten_dict(variables).items()}


def as_variables(w):
    """Flat ``/``-joined weights as the flax tree the model applies."""
    import flax.traverse_util as tu

    return {"params": tu.unflatten_dict({tuple(k.split("/")): jnp.asarray(v)
                                          for k, v in w.items()})}


def forward(model, variables, x):
    return np.asarray(jax.jit(model.apply)(variables, jnp.asarray(x)[None, ..., None]))[0]


@pytest.fixture(scope="module")
def small():
    model = get_model("unet3d_gcr", **SMALL)
    return model, init_params(model, (1, 1, 4, 4, 1))


def test_widths_flops_and_multiple_are_the_source_defaults():
    model = UNet3DGCR(pool_kernel_size=[1, 2, 2])
    assert model.input_multiple() == (1, 8, 8)
    assert [w[1:3] for w in model.widths()] == [
        (1, 32), (32, 64), (64, 64), (64, 128), (128, 128), (128, 256), (256, 256),
        (256, 512), (768, 256), (256, 256), (384, 128), (128, 128), (192, 64),
        (64, 64), (64, 1)]
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 1, 8, 8, 1)))
    kernels = [a for p, a in jax.tree_util.tree_flatten_with_path(variables)[0]
               if "kernel" in jax.tree_util.keystr(p)]
    assert sum(int(np.prod(a.shape)) for a in kernels) == 16_313_248
    per_voxel = model.forward_flops((40, 320, 512)) / (40 * 320 * 512)
    assert per_voxel == pytest.approx(3.26432e6, rel=1e-6)
    cfg = {"f_maps": 64, "num_levels": 4, "pool_kernel_size": [1, 2, 2]}
    assert reference_unet.forward_flops(cfg, (40, 320, 512)) == model.forward_flops(
        (40, 320, 512))
    assert UNet3DGCR(pool_kernel_size=2).input_multiple() == (8, 8, 8)
    assert UNet3DGCR(num_levels=3, pool_kernel_size=(1, 2, 2)).input_multiple() == (1, 4, 4)


def test_drawn_weights_fit_the_program_and_leave_no_leaf_trivial(small):
    _, variables = small
    made = flat_weights(variables)
    drawn = reference_unet.draw_weights(SMALL, np.random.default_rng(7))
    assert {k: v.shape for k, v in drawn.items()} == {k: v.shape for k, v in made.items()}
    assert all(v.dtype == np.float32 for v in drawn.values())
    for k, v in drawn.items():
        if k.endswith("groupnorm/scale"):
            assert (v != 1).all() and (v >= 0.5).all() and (v <= 1.5).all(), k
        elif k.endswith("bias"):
            assert (v != 0).all() and (np.abs(v) <= 2).all(), k
    assert (np.abs(drawn["final_conv/bias"]) >= 1).all()
    again = reference_unet.draw_weights(SMALL, np.random.default_rng(7))
    assert all(np.array_equal(drawn[k], again[k]) for k in drawn)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_model_alone_matches_the_reference(monkeypatch, small, operands):
    """On drawn weights: every GroupNorm affine and the head's bias count."""
    model, _ = small
    w = reference_unet.draw_weights(SMALL, np.random.default_rng(11))
    if operands == "float32":
        float32_operands(monkeypatch)
    x = np.random.default_rng(3).random((6, 24, 40)).astype(np.float32)
    got = forward(model, as_variables(w), x)[..., 0]
    want = reference_unet.forward_logits(x, w, SMALL)[0]
    if operands == "float32":
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    else:
        assert np.abs(got - want).max() < unet_pred.LOGIT_TOL
        assert np.abs(got - want).max() > 1e-3   # bfloat16 is not float32


def test_levels_run_under_their_scopes(small):
    model, variables = small
    text = jax.jit(model.apply).lower(variables, jnp.zeros((1, 2, 8, 8, 1))).as_text(
        debug_info=True)
    for scope in ("unet.enc0", "unet.enc2", "unet.dec1", "unet.dec0", "unet.head"):
        assert f"/{scope}/" in text, scope


@pytest.fixture
def workspace(tmp_path):
    config_dir = str(tmp_path / "config")
    os.makedirs(config_dir)
    return config_dir, str(tmp_path)


def run_inference(workspace, raw, *, block, halo, model=SMALL, run="a", **extra):
    """One inference job over ``raw``; its map, manifest and tmp folder."""
    from cluster_tools_tpu.tasks.inference import InferenceWorkflow

    config_dir, root = workspace
    tmp_folder = os.path.join(root, f"tmp_{run}")
    path = os.path.join(root, f"data_{run}.zarr")
    f = file_reader(path)
    f.require_dataset("raw", shape=raw.shape, chunks=tuple(block), dtype="float32")[...] = raw
    wf = InferenceWorkflow(
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=2, target="local",
        input_path=path, input_key="raw", output_path=path, output_key="pred",
        model={"name": "unet3d_gcr", **model}, halo=list(halo),
        normalize_range=[0.0, 1.0], block_shape=list(block), sharded_batch=1, **extra)
    assert build([wf])
    manifest = json.load(open(glob.glob(os.path.join(tmp_folder, "inference.*.success.json"))[0]))
    return file_reader(path, "r")["pred"][...], manifest, tmp_folder


def test_workflow_on_an_anisotropic_stack_matches_the_reference(workspace):
    """Every block of a 29 x 40 x 72 stack through ``cli``'s workflow, each
    against the reference on the window the kernel saw."""
    from benchmark import data
    from benchmark import reference_two_pass as grid

    vol = np.asarray(data.membrane_volume(5, 0, (29, 40, 72), 12), np.float32)
    block, halo = [8, 16, 32], [2, 8, 8]
    pred, manifest, tmp = run_inference(workspace, vol, block=block, halo=halo)
    assert pred.shape == (1,) + vol.shape and np.isfinite(pred).all()
    assert manifest["outer_shape"] == [12, 32, 48]
    w = reference_unet.recorded_weights(tmp)
    deviations = []
    for _, pos in grid.blocks_of(vol.shape, block):
        lo, hi, olo, ohi = grid.unit_bounds(pos, vol.shape, block, halo)
        x = reference_unet.unit_input(vol, olo, ohi, manifest["outer_shape"], [0.0, 1.0])
        logits = reference_unet.forward_logits(x, w, SMALL)
        inner = (slice(None),) + tuple(slice(a - o, b - o) for a, b, o in zip(lo, hi, olo))
        got = unet_pred.logits_off(
            pred[(slice(None),) + tuple(slice(a, b) for a, b in zip(lo, hi))], logits[inner])
        assert got["off"] == 0, (pos, got)
        deviations.append(got["max"])
    assert 1e-4 < max(deviations) < unet_pred.LOGIT_TOL


def test_outer_block_rounds_per_axis_and_counts_the_pass(workspace):
    vol = np.random.default_rng(1).random((9, 30, 26)).astype(np.float32)
    pred, manifest, tmp = run_inference(workspace, vol, block=[5, 13, 11], halo=[1, 2, 3])
    # (5 + 2, 13 + 4, 11 + 6) rounded to (1, 4, 4): z is not rounded
    assert manifest["outer_shape"] == [7, 20, 20]
    n = 2 * 3 * 3
    # one lane a dispatch per device (the tests' CPU backend has eight):
    # the last dispatch carries padding lanes, which count
    width = len(jax.devices())
    assert manifest["n_blocks"] == n
    assert manifest["dispatches"] == -(-n // width)
    assert manifest["lanes"] == manifest["dispatches"] * width
    assert manifest["outer_voxels"] == n * 7 * 20 * 20
    assert manifest["padded_voxels"] == manifest["lanes"] * 7 * 20 * 20
    assert manifest["inner_voxels"] == vol.size
    assert manifest["flops_per_lane"] == get_model("unet3d_gcr", **SMALL).forward_flops(
        (7, 20, 20))
    io = json.load(open(os.path.join(tmp, "io_metrics.json")))["tasks"]
    counts = next(v for k, v in io.items() if k.startswith("inference."))
    assert counts["lanes"] == manifest["lanes"]
    assert counts["padded_voxels"] == manifest["padded_voxels"]
    assert counts["flops"] == manifest["lanes"] * manifest["flops_per_lane"]
    assert np.isfinite(pred).all() and 0 <= pred.min() and pred.max() <= 1


def test_random_weights_are_recorded_and_made_by_one_small_jitted_init(workspace,
                                                                       monkeypatch):
    calls = []
    inner = UNet3DGCR.init

    def init(self, rng, x, *a, **kw):
        calls.append((tuple(x.shape), isinstance(x, jax.core.Tracer)))
        return inner(self, rng, x, *a, **kw)

    monkeypatch.setattr(UNet3DGCR, "init", init)
    vol = np.random.default_rng(2).random((8, 32, 32)).astype(np.float32)
    _, manifest, tmp = run_inference(workspace, vol, block=[8, 16, 16], halo=[0, 8, 8])
    # one init, traced under jit, on the smallest input the net accepts
    # (the (1, 4, 4) multiple), not on a 8 x 32 x 32 outer block
    assert calls == [((1, 1, 4, 4, 1), True)]
    recorded = reference_unet.recorded_weights(tmp)
    monkeypatch.setattr(UNet3DGCR, "init", inner)
    made = flat_weights(init_params(get_model("unet3d_gcr", **SMALL), (1, 1, 4, 4, 1)))
    assert recorded.keys() == made.keys()
    for k in made:
        np.testing.assert_array_equal(recorded[k], made[k])
    assert manifest["weights"]["source"] == "init"
    assert manifest["weights"]["params"] == sum(v.size for v in made.values())


def test_a_recorded_checkpoint_reproduces_the_map(workspace):
    """The npz the task records, fed back as ``checkpoint_path``, gives the
    same map to the bit."""
    vol = np.random.default_rng(4).random((8, 16, 32)).astype(np.float32)
    first, _, tmp = run_inference(workspace, vol, block=[8, 16, 16], halo=[0, 4, 4])
    (ckpt,) = glob.glob(os.path.join(tmp, "inference.*.weights.npz"))
    again, manifest, _ = run_inference(workspace, vol, block=[8, 16, 16], halo=[0, 4, 4],
                                       checkpoint_path=ckpt, run="b")
    assert manifest["weights"]["source"] == "checkpoint"
    np.testing.assert_array_equal(first, again)


def test_a_drawn_checkpoint_through_the_workflow_matches_the_reference(workspace, tmp_path):
    """Weights drawn from a seed, handed to the task as ``checkpoint_path``
    (the flat npz it records): every block against the reference on them."""
    from benchmark import reference_two_pass as grid

    vol = np.random.default_rng(6).random((10, 24, 40)).astype(np.float32)
    block, halo = [5, 16, 24], [1, 4, 4]
    w = reference_unet.draw_weights(SMALL, np.random.default_rng(12))
    ckpt = str(tmp_path / "drawn.weights.npz")
    reference_unet.write_weights(ckpt, w)
    pred, manifest, _ = run_inference(workspace, vol, block=block, halo=halo,
                                      checkpoint_path=ckpt)
    assert manifest["weights"]["source"] == "checkpoint"
    assert manifest["weights"]["params"] == sum(v.size for v in w.values())
    deviations = []
    for _, pos in grid.blocks_of(vol.shape, block):
        lo, hi, olo, ohi = grid.unit_bounds(pos, vol.shape, block, halo)
        x = reference_unet.unit_input(vol, olo, ohi, manifest["outer_shape"], [0.0, 1.0])
        logits = reference_unet.forward_logits(x, w, SMALL)
        inner = (slice(None),) + tuple(slice(a - o, b - o) for a, b, o in zip(lo, hi, olo))
        got = unet_pred.logits_off(
            pred[(slice(None),) + tuple(slice(a, b) for a, b in zip(lo, hi))], logits[inner])
        assert got["off"] == 0 and got["saturated"] == 0, (pos, got)
        deviations.append(got["max"])
    assert 1e-4 < max(deviations) < unet_pred.LOGIT_TOL


# --------------------------------------------------------------------------
# a torch twin of pytorch-3dunet's UNet3D, with its state_dict's names
# --------------------------------------------------------------------------


def torch_pytorch_3dunet(f_maps, num_levels, pool, num_groups=8, out_channels=1):
    import torch
    import torch.nn as tnn
    import torch.nn.functional as F

    class SingleConv(tnn.Sequential):
        def __init__(self, a, b):
            super().__init__()
            self.add_module("groupnorm", tnn.GroupNorm(1 if a < num_groups else num_groups, a))
            self.add_module("conv", tnn.Conv3d(a, b, 3, padding=1, bias=False))
            self.add_module("ReLU", tnn.ReLU())

    class DoubleConv(tnn.Sequential):
        def __init__(self, a, b, encoder):
            super().__init__()
            m = max(b // 2, a) if encoder else b
            self.add_module("SingleConv1", SingleConv(a, m))
            self.add_module("SingleConv2", SingleConv(m, b))

    class Encoder(tnn.Module):
        def __init__(self, a, b, pooling):
            super().__init__()
            self.pooling = tnn.MaxPool3d(pool) if pooling else None
            self.basic_module = DoubleConv(a, b, encoder=True)

        def forward(self, x):
            return self.basic_module(self.pooling(x) if self.pooling else x)

    class Decoder(tnn.Module):
        def __init__(self, a, b):
            super().__init__()
            self.basic_module = DoubleConv(a, b, encoder=False)

        def forward(self, skip, x):
            x = F.interpolate(x, size=skip.shape[2:], mode="nearest")
            return self.basic_module(torch.cat((skip, x), dim=1))

    feats = [f_maps * 2 ** l for l in range(num_levels)]

    class UNet3D(tnn.Module):
        def __init__(self):
            super().__init__()
            self.encoders = tnn.ModuleList(
                [Encoder(1 if l == 0 else feats[l - 1], feats[l], l > 0)
                 for l in range(num_levels)])
            self.decoders = tnn.ModuleList(
                [Decoder(feats[l] + feats[l + 1], feats[l])
                 for l in reversed(range(num_levels - 1))])
            self.final_conv = tnn.Conv3d(feats[0], out_channels, 1)

        def forward(self, x):
            skips = []
            for enc in self.encoders:
                x = enc(x)
                skips.insert(0, x)
            for dec, skip in zip(self.decoders, skips[1:]):
                x = dec(skip, x)
            return self.final_conv(x)

    net = UNet3D()
    # GroupNorm's affine off its (1, 0) start, so the import is tested
    with torch.no_grad():
        for mod in net.modules():
            if isinstance(mod, tnn.GroupNorm):
                mod.weight.uniform_(0.5, 1.5)
                mod.bias.uniform_(-0.5, 0.5)
    return net.eval()


def test_pytorch_3dunet_checkpoint_imports_to_the_torch_forward(tmp_path, monkeypatch):
    import torch

    from cluster_tools_tpu.tasks.inference import load_checkpoint

    torch.manual_seed(0)
    net = torch_pytorch_3dunet(8, 3, (1, 2, 2))
    path = str(tmp_path / "best_checkpoint.pytorch.pt")
    torch.save({"model_state_dict": net.state_dict()}, path)
    model = get_model("unet3d_gcr", **SMALL)
    variables = load_checkpoint(path, model, (1, 1, 4, 4, 1))

    float32_operands(monkeypatch)
    x = np.random.default_rng(0).random((6, 16, 24)).astype(np.float32)
    got = forward(model, variables, x)[..., 0]
    with torch.no_grad():
        want = net(torch.from_numpy(x)[None, None]).numpy()[0, 0]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_pytorch_3dunet_import_names_the_tensor_that_does_not_fit():
    import torch

    from cluster_tools_tpu.models.torch_import import pytorch_3dunet_to_flax

    torch.manual_seed(0)
    state = torch_pytorch_3dunet(8, 3, (1, 2, 2)).state_dict()
    model = get_model("unet3d_gcr", **SMALL)
    deeper = get_model("unet3d_gcr", **dict(SMALL, num_levels=4))
    with pytest.raises(ValueError, match="decoders.2.basic_module.SingleConv1"):
        pytorch_3dunet_to_flax(state, deeper)
    extra = dict(state, **{"encoders.0.basic_module.SingleConv1.conv.bias": torch.zeros(4)})
    with pytest.raises(ValueError, match="SingleConv1.conv.bias"):
        pytorch_3dunet_to_flax(extra, model)
    wide = get_model("unet3d_gcr", **dict(SMALL, f_maps=16))
    with pytest.raises(ValueError, match="encoders.0.basic_module.SingleConv1.conv.weight"):
        pytorch_3dunet_to_flax(state, wide)
