"""Compile the main-path Pallas kernels for a TPU v5e at CT-study size.

Interpret mode cannot see what the chip's compiler refuses: a whole-array
input staged in VMEM, a slice not aligned to the (8, 128) tiling, or a
one-lane operand that XLA relays out to 128 lanes on the way in and out.
Each test here compiles one kernel family that ``method="auto"`` reaches
on the chip — stencil, bank (K=12), depthwise (K=3), moment — for a
described (not attached) v5e at the chip smoke's 256×512×512 float32
volume, and checks that the kernel is really there (``tpu_custom_call``)
and that the program's memory stays a small multiple of its own input
and output bytes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite runs under
several workers that all import this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.filters import curvature_bank
from repro.core.grid import make_quasi_grid
from repro.kernels import ops

#: the chip smoke's CT study: 256 slices of 512×512
CT = (256, 512, 512)
#: one chip's slab of the four-chip RM state: 80 slices and a 3-plane
#: halo on each side, the 2048×2048 plane padded for a 7-tap Gaussian
RM_SLAB = (86, 2054, 2054)
#: the same slab as the 3×3×3 gradient bank reads it: a 1-plane halo
RM_GRAD_SLAB = (82, 2050, 2050)
TILE_ROWS = 256
#: argument + output + temp bytes over the call's own input + output
#: bytes; a one-lane operand relaid out to 128 lanes would be ~128
MEM_BOUND = 4.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of any cache the env turned on
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _check(lowered, in_shape, out_shape):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    own = 4 * (int(np.prod(in_shape)) + int(np.prod(out_shape)))
    assert used <= MEM_BOUND * own, (used / own, m)


def test_stencil_compiles_at_ct_size(one_chip):
    grid = make_quasi_grid(CT, (3, 3, 3), 1, "same", 1)
    lowered = ops.fused_stencil.lower(
        _sds(CT, one_chip), grid=grid, weights=_sds((27,), one_chip),
        pad_value="edge", interpret=False, tile_rows=TILE_ROWS)
    _check(lowered, CT, CT)


def test_bank_k12_compiles_at_ct_size(one_chip):
    grid = make_quasi_grid(CT, (3, 3, 3), 1, "same", 1)
    K = curvature_bank(3).shape[1]
    assert K == 12
    lowered = ops.fused_stencil_bank.lower(
        _sds(CT, one_chip), grid=grid, weight_matrix=_sds((27, K), one_chip),
        pad_value="edge", interpret=False, tile_rows=TILE_ROWS)
    _check(lowered, CT, CT + (K,))


def test_depthwise_k3_compiles_at_ct_size(one_chip):
    # the second 1-D pass of the composed gaussian∘gradient bank
    grid = make_quasi_grid(CT, (1, 7, 1), 1, "valid", 1)
    lowered = ops.fused_stencil_depthwise.lower(
        _sds(CT + (3,), one_chip), grid=grid,
        weights=_sds((7, 3), one_chip), pad_value=0.0, interpret=False,
        tile_rows=TILE_ROWS)
    _check(lowered, CT + (3,), grid.out_shape + (3,))


def test_moment_compiles_at_ct_size(one_chip):
    # per-channel variance of a 3-channel gradient field, rows of 512
    shape = (3, CT[0] * CT[1], CT[2])
    lowered = ops.fused_moment_sums.lower(
        _sds(shape, one_chip), interpret=False, order=2)
    _check(lowered, shape, (3,))


def _volume_loops(text, numel):
    """The ``while`` loops XLA writes to relay out an f32 array of at
    least ``numel`` elements (a volume, not a plane)."""
    import re

    return [line for line in text.splitlines() if " while(" in line
            and any(int(np.prod([int(d) for d in dims.split(",")])) >= numel
                    for dims in re.findall(r"f32\[([\d,]+)\]", line))]


@pytest.mark.parametrize("shape,taps,K,padding,loops,temp_gib", [
    (RM_SLAB, 7, 1, "valid", 2, 6.125),
    (CT, 9, 3, "valid", 0, 1.6),
    (CT, 9, 3, "same", 0, 1.75),
], ids=["rm-slab", "ct-interior", "ct-same"])
def test_separable_group_keeps_rows_across_passes(one_chip, shape, taps, K,
                                                  padding, loops, temp_gib):
    """A 'valid' separable group is laid out as the kernels' rows once and
    back once, not around each of its three per-dim passes: at the RM
    slab's lane-misaligned width that leaves 2 volume relayout loops (4
    when each pass had its own pair), and at the lane-aligned CT interior
    it halves the temporaries (2.91 GiB with a crop and re-pad between
    passes).  A 'same' group around the lane-aligned CT plane keeps a pad
    and crop per pass: one padded volume's 520×520 planes would cost a
    relayout loop and 3.06 GiB of temporaries against 1.70."""
    grid = make_quasi_grid(shape, (taps,) * 3, 1, padding, 1)
    lowered = ops.fused_separable_bank.lower(
        _sds(shape, one_chip), grid=grid,
        factors=tuple(_sds((taps, K), one_chip) for _ in range(3)),
        pad_value="edge" if padding == "same" else 0.0, interpret=False)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(_volume_loops(text, int(np.prod(shape)))) <= loops
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= temp_gib * 2 ** 30, temp / 2 ** 30


def test_curvature_handoff_relayouts_one_channel(one_chip):
    """The det combine runs on the K=12 bank's own rows: the loops XLA
    writes to lay rows out as a volume carry one channel, not twelve."""
    import re

    from repro.core.filters import _curvature_combine

    grid = make_quasi_grid(CT, (3, 3, 3), 1, "same", 1)
    lowered = ops.fused_stencil_bank.lower(
        _sds(CT, one_chip), grid=grid, weight_matrix=_sds((27, 12), one_chip),
        pad_value="edge", interpret=False, tile_rows=TILE_ROWS,
        pointwise=_curvature_combine(3))
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    padded = int(np.prod(grid.padded_shape))
    carried = [int(np.prod([int(d) for d in dims.split(",")]))
               for line in text.splitlines() if " while(" in line
               for dims in re.findall(r"f32\[([\d,]+)\]", line)]
    assert carried and max(carried) <= 1.2 * padded, max(carried) / padded


@pytest.mark.parametrize("shape,bank,padding", [
    (CT, "curvature", "same"),
    (RM_GRAD_SLAB, "gradient", "valid"),
], ids=["ct-curvature", "rm-slab-gradient"])
def test_bank_with_support_compiles(one_chip, shape, bank, padding):
    """A derivative bank with its zero products skipped: the K=12
    curvature bank at CT size, and the K=3 gradient bank on one chip's
    RM slab, both unroll only their kept (tap, k) products."""
    from repro.core.filters import difference_stencils
    from repro.kernels.melt_stencil import weight_support

    W = (curvature_bank(3) if bank == "curvature"
         else difference_stencils(3)[0].astype(np.float32))
    support = weight_support(W)
    assert support is not None
    K = W.shape[1]
    grid = make_quasi_grid(shape, (3, 3, 3), 1, padding, 1)
    lowered = ops.fused_stencil_bank.lower(
        _sds(shape, one_chip), grid=grid, weight_matrix=_sds((27, K), one_chip),
        pad_value="edge" if padding == "same" else 0.0, interpret=False,
        support=support)
    _check(lowered, shape, grid.out_shape + (K,))
