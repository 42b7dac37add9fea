"""The bytes and operations per graph, against hand counts."""
import pytest

from bench import manifest, reference, work

CT = (256, 512, 512)
VOX = 256 * 512 * 512
SAME = [("gaussian", {"sigma": 1.5}), ("gradient", {}),
        ("moments", {"order": 2})]


def test_moments_graph_reads_its_input_once():
    # 4 bytes a voxel in; the state out: 5 float32 leaves × 3 channels
    assert work.least_bytes(CT, SAME) == 4 * VOX + 5 * 4 * 3


def test_curvature_reads_and_writes_one_volume():
    assert work.least_bytes(CT, [("curvature", {})]) == 2 * 4 * VOX


def test_gradient_output_has_three_channels():
    g = [("gaussian", {"sigma": 1.5}), ("gradient", {})]
    assert work.least_bytes((155, 240, 240), g) == (4 + 12) * 155 * 240 * 240


@pytest.mark.parametrize("graph,per_voxel", [
    # separable 7-tap Gaussian: 3 axes × 7 taps × (mul + add)
    ([("gaussian", {"sigma": 1.5})], 42),
    ([("gaussian", {"sigma": 1.0})], 30),
    # three central differences, a subtraction and a halving each
    ([("gradient", {})], 6),
    (SAME, 42 + 6 + 12),
    ([("curvature", {})], 47),
])
def test_ops_per_voxel(graph, per_voxel):
    assert work.ops(CT, graph) == per_voxel * VOX


@pytest.mark.parametrize("sigma,r", [(0.4, 1), (1.0, 2), (1.5, 3), (2.2, 5)])
def test_gaussian_radius_is_the_programs_footprint(sigma, r):
    # the graph's default footprint is 2·ceil(2σ)+1 wide, at least 3
    assert manifest.module("stages", "gaussian").radius({"sigma": sigma}) == r
    assert max(3, 2 * int(-(-2 * sigma // 1)) + 1) == 2 * r + 1


def test_block_rows_divides_the_volume():
    for shape in (CT, (155, 240, 240), (320, 2048, 2048)):
        n = reference.block_rows(shape)
        assert shape[0] % n == 0 and n * shape[1] * shape[2] <= 1 << 25
