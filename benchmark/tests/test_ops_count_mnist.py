"""``ops_count_mnist.py`` against a hand count at MnistRandomFFT's
published widths, and the adapter's ``ops()`` built from it."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, ops_count, ops_count_mnist  # noqa: E402


def test_fft_per_image_against_a_hand_count():
    got = ops_count_mnist.fft_per_image(784, 1024, 200)
    # 2.5 N log2 N of a 1024-point real FFT, 200 of them
    assert got["flops"] == 200 * 2.5 * 1024 * 10 == 5_120_000
    # 784 float32 in and 512 out, a branch
    assert got["bytes"] == 200 * 4 * (784 + 512) == 1_036_800
    assert ops_count_mnist.features(1024, 200) == 102_400


def test_the_adapters_ops_are_the_cells():
    _, cell, cfg = harness.find_cell("mnist-fft.fit")
    adapter = harness.load_module("adapters", cfg["adapter"])
    got = adapter.ops(cfg, cell)
    n = cell["n"]
    assert got["fft_flops"] == n * 5_120_000 == got["featurize_flops"]
    assert got["fft_bytes"] == n * 1_036_800
    # 102,400 features are 50 blocks of 2048: no blocked width beyond them
    assert got["solver_flops"] == ops_count.solver_flops(n, 102400, 10, 2048, 1)
    assert got["solver_bytes"] == ops_count.solver_bytes(n, 102400, 10, 2048, 1)
    assert cfg["num_features"] == 102400 == ops_count_mnist.features(cfg["padded"],
                                                                       cfg["num_ffts"])
    # bound by bytes at the published peaks: 1.27 us an image
    peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))["peaks"]["TPU v5 lite"]
    least, bound = ops_count.roofline_seconds(got["fft_flops"], got["fft_bytes"], peaks, 1)
    assert bound == "bytes" and abs(least / n - 1_036_800 / 819e9) < 1e-15
