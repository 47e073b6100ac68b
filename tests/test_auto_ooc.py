"""Auto out-of-core: no fit() may OOM the chip (round-4 review item 2).

The profiled materialization pass holds the footprint estimate; fit()'s
pre-flight acts on it — auto-spilling large array sources to the
streaming path (features spill to the FeatureBlockStore) or, with
KEYSTONE_AUTO_SPILL=0, refusing cleanly with the predicted bytes.
Reference: workflow/AutoCacheRule.scala (memory-budget decisions belong
to the optimizer, not the user)."""

import numpy as np
import pytest

from keystone_tpu.loaders.imagenet import ImageNetLoader
from keystone_tpu.pipelines.imagenet_sift_lcs_fv import Config, ImageNetSiftLcsFV
from keystone_tpu.workflow.pipeline import PreflightOOMError


def _cfg():
    return Config(
        num_classes=4,
        synthetic_n=128,
        image_size=64,
        gmm_k=4,
        pca_dims=8,
        descriptor_samples_per_image=8,
        gmm_iters=2,
        num_epochs=1,
        solver_block_size=64,
    )


def _fit_predict(cfg, train, test_imgs):
    fitted = ImageNetSiftLcsFV.build(cfg, train.data, train.labels).fit()
    return fitted(test_imgs).get().numpy()


def test_auto_spill_completes_and_matches_in_memory(monkeypatch):
    cfg = _cfg()
    train = ImageNetLoader.synthetic(
        cfg.synthetic_n, cfg.num_classes, size=(64, 64), seed=1
    )
    test = ImageNetLoader.synthetic(16, cfg.num_classes, size=(64, 64), seed=2)
    want = _fit_predict(cfg, train, test.data)

    # shrink the HBM budget so the (1.6 MB) image source is over budget:
    # fit must COMPLETE via auto-spill, bit-matching the in-memory fit
    # (the stream path's parity is the e2e-tested --stream machinery)
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET_BYTES", str(200_000))
    got = _fit_predict(cfg, train, test.data)
    np.testing.assert_array_equal(got, want)


def test_auto_spill_disabled_refuses_cleanly(monkeypatch):
    cfg = _cfg()
    train = ImageNetLoader.synthetic(
        cfg.synthetic_n, cfg.num_classes, size=(64, 64), seed=1
    )
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET_BYTES", str(200_000))
    monkeypatch.setenv("KEYSTONE_AUTO_SPILL", "0")
    with pytest.raises(PreflightOOMError) as ei:
        ImageNetSiftLcsFV.build(cfg, train.data, train.labels).fit()
    msg = str(ei.value)
    assert "GB" in msg and "--stream" in msg  # predicted bytes + pointer
