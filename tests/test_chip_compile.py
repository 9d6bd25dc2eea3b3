"""Compile the main path's kernels for the real chip, without the chip.

The TPU's compiler is installed here and compiles for a *described* v5e:2x2
(``/opt/skills/guides/on-chip-measurement`` section 2): Mosaic refuses what
interpret mode lets through — an unaligned slice, too much VMEM, an illegal
vreg cast — and these tests are where a later PR finds that out for free.
Tier-1 otherwise only ever runs the ``xla`` twins and the Pallas kernels with
``interpret=True``; the programs below are the ones ``impl="auto"`` resolves
to on the chip (``chip_smoke.py`` runs them there).

Nothing runs and nothing is timed: a compile that passes is not a chip run.

All tests of this kind live in THIS file, and the topology is described
inside a module-scoped fixture: only one process may load the TPU's library,
so under pytest-xdist only the worker that is handed this file may do it,
and never while a module is being imported.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cluster_tools_tpu.ops import pallas_kernels as pk

# BASELINE config 2's outer block: 64^3 blocks with halo 32
SHAPE = (128, 128, 128)
TILE = (16, 16, 128)


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one described v5e chip; the persistent compile cache is
    off while this module runs (an entry written for a described chip cannot
    be read back without one — the next compile would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *avals, **static):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in avals
    ]
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()


def _n_tiles():
    return (SHAPE[0] // TILE[0]) * (SHAPE[1] // TILE[1]) * (SHAPE[2] // TILE[2])


KERNELS = {
    "tile_ccl": (
        pk.tile_ccl_pallas, [(SHAPE, jnp.bool_)], dict(tile=TILE),
    ),
    "tile_ws_propagate": (
        pk.tile_ws_propagate_pallas,
        [(SHAPE, jnp.int32), (SHAPE, jnp.int32)],
        dict(tile=TILE),
    ),
    # the cascade keeps a whole line of the processed axis in VMEM: the lane
    # axis (2) and a major axis (0) tile differently
    "edt_cascade_lane_axis": (
        pk.edt_cascade_pallas, [(SHAPE, jnp.float32)],
        dict(axis=2, radius=32, w=1.0, big=1e10),
    ),
    "edt_cascade_major_axis": (
        pk.edt_cascade_pallas, [(SHAPE, jnp.float32)],
        dict(axis=0, radius=32, w=1.0, big=1e10),
    ),
    "apply_remap": (
        pk.apply_remap_pallas,
        [(SHAPE, jnp.int32), ((_n_tiles(), 64), jnp.int32),
         ((_n_tiles(), 64), jnp.int32)],
        dict(tile=TILE, cap=64),
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, avals, static = KERNELS[name]
    compiled = _compile(fn, one_chip, *avals, **static)
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: no Mosaic kernel in the compiled program"
    )


@pytest.fixture
def tpu_default_backend(monkeypatch):
    """``impl="auto"`` sites ask ``jax.default_backend()``, which is the CPU
    during such a compile; answer as the chip would, here in the test — not
    through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# The two composite programs take 39 s and 158 s to compile for the chip
# (CHANGES.md PR 24) — more than tier-1 should carry per run — so they are
# tier-2; the five kernels above keep Mosaic's refusals covered in tier-1.
@pytest.mark.slow
def test_tiled_ccl_program_compiles_for_v5e(one_chip, tpu_default_backend):
    from cluster_tools_tpu.ops.tile_ccl import label_components_tiled

    compiled = _compile(
        label_components_tiled, one_chip, (SHAPE, jnp.bool_), impl="auto"
    )
    assert compiled.as_text().count("tpu_custom_call") >= 2  # ccl + remap


@pytest.mark.slow
def test_dt_watershed_program_compiles_for_v5e(one_chip, tpu_default_backend):
    """The blockwise watershed's per-block program as the chip gets it:
    Mosaic EDT + seed CCL + flow + remap kernels, capacity fill."""
    from cluster_tools_tpu.ops.tile_ws import dt_watershed_tiled, resolved_modes

    assert resolved_modes("auto")["impl"] == "pallas"
    assert resolved_modes("auto")["fill_mode"] == "capacity"
    compiled = _compile(
        dt_watershed_tiled, one_chip, (SHAPE, jnp.float32),
        threshold=0.5, dt_max_distance=32.0, impl="auto",
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9  # one chip's HBM
