"""``ops_count_cifar.py`` against a hand count at RandomPatchCifar's
published widths, and the adapter's ``ops()`` built from it."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, ops_count, ops_count_cifar  # noqa: E402


def test_conv_per_image_against_a_hand_count():
    got = ops_count_cifar.conv_per_image(32, 3, 6, 10000, 14, 13)
    # 27 x 27 = 729 positions of 6 x 6 x 3 = 108 numbers against 10,000 filters
    assert got["gemm_flops"] == 2 * 729 * 108 * 10000 == 1_574_640_000
    box, pointwise, pooling = 4 * 729 * 108, 6 * 729 * 10000, 2 * 4 * 14 * 14 * 10000
    assert (box, pointwise, pooling) == (314_928, 43_740_000, 15_680_000)
    assert got["flops"] == 1_574_640_000 + box + pointwise + pooling == 1_634_374_928
    assert got["bytes"] == 32 * 32 * 3 + 4 * 80000 == 323_072
    assert got["filter_bytes"] == 4 * 108 * 10000
    assert ops_count_cifar.features(32, 6, 10000, 14, 13) == 80000
    assert ops_count_cifar.pooled(32, 6, 14, 13) == (27, 2)
    assert ops_count_cifar.pooled(32, 6, 13, 13) == (27, 2)  # the older port's pooling


def test_filter_learning_is_small_beside_the_convolution():
    learn = ops_count_cifar.filter_learning(100000, 108, 10000)
    assert learn == 2 * 100000 * 108**2 + 11 * 108**3 + 2 * 10000 * 108**2
    assert learn < 2 * ops_count_cifar.conv_per_image(32, 3, 6, 10000, 14, 13)["flops"]


def test_the_adapters_ops_are_the_cells():
    _, cell, cfg = harness.find_cell("cifar-rp.fit")
    adapter = harness.load_module("adapters", cfg["adapter"])
    got = adapter.ops(cfg, cell)
    n = cell["n"]
    assert got["conv_flops"] == n * 1_634_374_928
    assert got["conv_bytes"] == n * 323_072 + 4_320_000
    assert got["featurize_flops"] > got["conv_flops"]
    # the solver is counted at the blocked width, 20 blocks of 4096
    assert got["solver_flops"] == ops_count.solver_flops(n, 81920, 10, 4096, 1)
    assert cfg["num_features"] == 80000 == ops_count_cifar.features(
        cfg["image_size"], cfg["patch_size"], cfg["num_filters"], cfg["pool_size"],
        cfg["pool_stride"])
    # bound by flops at the published peaks
    peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))["peaks"]["TPU v5 lite"]
    least, bound = ops_count.roofline_seconds(got["conv_flops"], got["conv_bytes"], peaks, 1)
    assert bound == "flops" and abs(least / n - 8.296e-6) < 1e-8
