"""Out-of-core end to end: loader → StreamDataset → app → CLI.

The reference's scaling story starts at the loader (ImageNetLoader
streams tar shards through RDD partitions into the whole pipeline —
SURVEY.md §2.5/§3.4); these tests pin the TPU analogue: tar shards →
StreamDataset → two-branch SIFT/LCS+FV featurization → out-of-core
BlockWeightedLS spill-fit, producing the SAME model as the in-memory
path, with the feature matrix never materialized in device memory.
"""

import io
import logging
import os
import tarfile

import numpy as np
import pytest

from keystone_tpu.loaders.csv_loader import CsvDataLoader
from keystone_tpu.loaders.imagenet import ImageNetLoader
from keystone_tpu.loaders.timit import TimitFeaturesDataLoader
from keystone_tpu.workflow import Dataset, StreamDataset


def _write_jpeg_tars(root, num_tars=3, per_tar=4, size=(48, 48), seed=0):
    """A multi-tar fixture of decodable JPEGs, one synset per tar."""
    from PIL import Image as PILImage

    rng = np.random.default_rng(seed)
    os.makedirs(root, exist_ok=True)
    # per-SYNSET base colors, well separated, so classes are learnable
    anchors = np.array(
        [[200, 60, 60], [60, 200, 60], [60, 60, 200], [200, 200, 60]],
        np.float32,
    )
    for t in range(num_tars):
        path = os.path.join(root, f"n{t:08d}.tar")
        base_color = anchors[t % len(anchors)]
        with tarfile.open(path, "w") as tf:
            for j in range(per_tar):
                # low-frequency texture so JPEG decode is near-lossless
                base = base_color + rng.uniform(-15, 15, size=(3,))
                img = np.tile(base, (*size, 1)) + rng.normal(0, 8, (*size, 3))
                pil = PILImage.fromarray(
                    np.clip(img, 0, 255).astype(np.uint8)
                )
                buf = io.BytesIO()
                pil.save(buf, format="JPEG", quality=95)
                data = buf.getvalue()
                info = tarfile.TarInfo(name=f"n{t:08d}_{j}.JPEG")
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))
    return root


# ------------------------------------------------------------- loaders


def test_imagenet_index_counts_members(tmp_path):
    root = _write_jpeg_tars(str(tmp_path / "tars"), num_tars=3, per_tar=4)
    entries = ImageNetLoader.index(root)
    assert len(entries) == 12
    labels = [e[3] for e in entries]
    assert labels == [0] * 4 + [1] * 4 + [2] * 4


def test_imagenet_stream_matches_load(tmp_path, mesh):
    root = _write_jpeg_tars(str(tmp_path / "tars"))
    size = (48, 48)
    mem = ImageNetLoader.load(root, size=size)
    st = ImageNetLoader.stream(root, size=size, batch_size=5)
    assert isinstance(st.data, StreamDataset)
    assert st.data.n == mem.data.n
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())
    got = np.concatenate(list(st.data.batches()))
    np.testing.assert_array_equal(got, mem.data.numpy())
    # re-iterable: a second sweep decodes the same pixels
    again = np.concatenate(list(st.data.batches()))
    np.testing.assert_array_equal(again, got)


def test_imagenet_stream_limit(tmp_path):
    root = _write_jpeg_tars(str(tmp_path / "tars"))
    st = ImageNetLoader.stream(root, size=(48, 48), batch_size=4, limit=7)
    assert st.data.n == 7 and st.labels.n == 7


def test_synthetic_stream_pixel_identical_to_synthetic(mesh):
    st = ImageNetLoader.synthetic_stream(24, 4, size=(48, 48), seed=1, batch_size=7)
    mem = ImageNetLoader.synthetic(24, 4, size=(48, 48), seed=1)
    np.testing.assert_array_equal(
        np.concatenate(list(st.data.batches())), mem.data.numpy()
    )
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())


def test_csv_stream_matches_load(tmp_path, mesh):
    rng = np.random.default_rng(0)
    mat = np.column_stack(
        [rng.integers(0, 5, size=23), rng.normal(size=(23, 7))]
    )
    path = str(tmp_path / "rows.csv")
    np.savetxt(path, mat, delimiter=",", fmt="%.6f")
    mem = CsvDataLoader.load(path)
    st = CsvDataLoader.stream(path, batch_size=6)
    assert st.data.n == 23
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())
    np.testing.assert_allclose(
        np.concatenate(list(st.data.batches())), mem.data.numpy(), rtol=1e-6
    )


def test_timit_stream_matches_load_npy(tmp_path, mesh):
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(31, 12)).astype(np.float32)
    labs = rng.integers(0, 9, size=31).astype(np.int64)
    fp, lp = str(tmp_path / "f.npy"), str(tmp_path / "l.npy")
    np.save(fp, feats)
    np.save(lp, labs)
    mem = TimitFeaturesDataLoader.load(fp, lp)
    st = TimitFeaturesDataLoader.stream(fp, lp, batch_size=8)
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())
    np.testing.assert_allclose(
        np.concatenate(list(st.data.batches())), mem.data.numpy()
    )


def test_column_sampler_stream_matches_inmemory(mesh):
    from keystone_tpu.ops import ColumnSampler

    rng = np.random.default_rng(3)
    descs = rng.normal(size=(20, 15, 6)).astype(np.float32)
    masks = (rng.uniform(size=(20, 15)) < 0.7).astype(np.float32)
    masks[:, 0] = 1.0  # every item keeps at least one valid descriptor
    cs = ColumnSampler(8, seed=5)
    mem = cs.apply_dataset(Dataset(descs, mask=Dataset(masks).array))
    batches = [
        (descs[:7], masks[:7]),
        (descs[7:12], masks[7:12]),
        (descs[12:], masks[12:]),
    ]
    st = cs.apply_dataset(StreamDataset(batches, n=20))
    np.testing.assert_allclose(st.numpy(), mem.numpy(), rtol=1e-6)


def test_column_sampler_host_stream_raises_typeerror(mesh):
    """A host-payload stream (text docs) must fail with the descriptive
    'featurize first' TypeError, not an AttributeError on list.ndim
    (ADVICE r3 low)."""
    from keystone_tpu.ops import ColumnSampler

    host = StreamDataset([["a doc", "b doc"]], n=2, host=True)
    with pytest.raises(TypeError, match="[Ff]eaturize to arrays"):
        ColumnSampler(4, seed=0).apply_dataset(host)


# ------------------------------------------------- end-to-end app parity


def _fv_config(stream: bool, **kw):
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import Config

    base = dict(
        num_classes=4,
        synthetic_n=24,
        image_size=48,
        gmm_k=4,
        pca_dims=16,
        num_epochs=2,
        descriptor_samples_per_image=16,
        solver_block_size=64,
        stream=stream,
        stream_batch_size=7,
    )
    base.update(kw)
    return Config(**base)


def test_imagenet_fv_stream_fit_matches_inmemory(mesh, caplog, monkeypatch):
    """The north-star gate: tar-shard-style streaming through the FULL
    two-branch pipeline produces the in-memory model's predictions,
    the features spill through a FeatureBlockStore, and the big stream
    is never materialized into device memory."""
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import ImageNetSiftLcsFV
    from keystone_tpu.workflow import blockstore

    cfg = _fv_config(stream=False)
    train_mem = ImageNetLoader.synthetic(24, 4, size=(48, 48), seed=1)
    test = ImageNetLoader.synthetic(8, 4, size=(48, 48), seed=2)
    fitted_mem = ImageNetSiftLcsFV.build(
        cfg, train_mem.data, train_mem.labels
    ).fit()
    pred_mem = fitted_mem(test.data).get().numpy()

    spills = []
    orig = blockstore.FeatureBlockStore.from_batches.__func__

    def spy(cls, directory, batches, n, block_size, dtype="float32"):
        store = orig(cls, directory, batches, n, block_size, dtype=dtype)
        spills.append((n, store.d))
        return store

    monkeypatch.setattr(
        blockstore.FeatureBlockStore, "from_batches", classmethod(spy)
    )
    train_st = ImageNetLoader.synthetic_stream(
        24, 4, size=(48, 48), seed=1, batch_size=7
    )
    with caplog.at_level(logging.WARNING, "keystone_tpu.workflow.dataset"):
        fitted_st = ImageNetSiftLcsFV.build(
            _fv_config(stream=True), train_st.data, train_st.labels
        ).fit()
        pred_st = fitted_st(test.data).get().numpy()
    assert spills and spills[0][0] == 24  # out-of-core spill path engaged
    assert not [
        r for r in caplog.records if "materializing StreamDataset" in r.message
    ], "a pipeline stage materialized the stream"
    np.testing.assert_array_equal(pred_st, pred_mem)


def test_imagenet_fv_app_entry_stream(mesh):
    """Through the app's run() entry point (the user-facing command)."""
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import ImageNetSiftLcsFV

    out = ImageNetSiftLcsFV.run(_fv_config(stream=True))
    assert out["pipeline"] == "ImageNetSiftLcsFV"
    assert 0.0 <= out["top5_error"] <= 1.0
    # the synthetic textures are learnable: streaming must not break fit
    assert out["accuracy"] > 0.5


def test_imagenet_fv_app_from_tar_fixture_stream(tmp_path, mesh):
    """One command fits from multi-tar shards via --stream: the loader
    indexes the tars, streams decode, and the fit goes out-of-core."""
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import ImageNetSiftLcsFV

    root = _write_jpeg_tars(
        str(tmp_path / "tars"), num_tars=3, per_tar=6, size=(48, 48)
    )
    cfg = _fv_config(
        stream=True, train_path=root, test_path=root, num_classes=3
    )
    out = ImageNetSiftLcsFV.run(cfg)
    # 3 flat-color synsets are separable by the LCS branch's color stats
    assert out["accuracy"] > 0.9


def test_voc_synthetic_stream_matches_synthetic(mesh):
    """Loader-level: VOC's synthetic stream is pixel- and label-identical
    to the in-memory synthetic set (the parity convention every loader
    follows)."""
    from keystone_tpu.loaders.voc import VOCLoader

    mem = VOCLoader.synthetic(18, size=(48, 48), seed=1)
    st = VOCLoader.synthetic_stream(18, size=(48, 48), seed=1, batch_size=5)
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())
    np.testing.assert_array_equal(
        np.concatenate(list(st.data.batches())), mem.data.numpy()
    )


def test_voc_app_stream_matches_inmemory(mesh):
    """VOCSIFTFisher --stream (the last of the eight apps, round-3 review
    weak-4): the streamed fit produces the in-memory fit's scores."""
    from keystone_tpu.pipelines.voc_sift_fisher import Config, VOCSIFTFisher

    base = dict(
        synthetic_n=18,
        image_size=48,
        gmm_k=4,
        pca_dims=16,
        descriptor_samples_per_image=16,
        solver_block_size=64,
        num_epochs=2,
    )
    out_mem = VOCSIFTFisher.run(Config(**base))
    out_st = VOCSIFTFisher.run(
        Config(**base, stream=True, stream_batch_size=5)
    )
    assert out_st["pipeline"] == "VOCSIFTFisher"
    # identical training pixels + deterministic fit → identical mAP
    np.testing.assert_allclose(out_st["mean_ap"], out_mem["mean_ap"], atol=1e-6)


def test_imagenet_augmented_eval_composes_with_stream(mesh):
    """--augmented-eval × --stream (round-3 review next-6): the 10-view
    augmented evaluation must run against a model fit from the streamed
    loader, matching the in-memory augmented run."""
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import ImageNetSiftLcsFV

    out_mem = ImageNetSiftLcsFV.run(_fv_config(stream=False, augmented_eval=True))
    out_st = ImageNetSiftLcsFV.run(_fv_config(stream=True, augmented_eval=True))
    np.testing.assert_allclose(
        out_st["top5_error"], out_mem["top5_error"], atol=1e-6
    )
    np.testing.assert_allclose(
        out_st["accuracy"], out_mem["accuracy"], atol=1e-6
    )


def test_timit_app_stream_matches_inmemory(mesh):
    from keystone_tpu.pipelines.timit import Config, TimitPipeline

    base = dict(
        num_cosine_features=256,
        cosine_block_size=128,
        num_classes=8,
        synthetic_n=256,
        num_epochs=2,
    )
    out_mem = TimitPipeline.run(Config(**base))
    out_st = TimitPipeline.run(Config(**base, stream=True, stream_batch_size=64))
    assert abs(out_st["accuracy"] - out_mem["accuracy"]) < 0.05


def test_cli_stream_flag(tmp_path, mesh, capsys):
    """bin-level: the CLI routes --stream through to the app."""
    from keystone_tpu import cli

    rc = cli.main(
        [
            "ImageNetSiftLcsFV",
            "--stream",
            "--synthetic-n",
            "16",
            "--num-classes",
            "4",
            "--image-size",
            "48",
            "--gmm-k",
            "4",
            "--pca-dims",
            "16",
        ]
    )
    assert rc == 0
    assert "ImageNetSiftLcsFV" in capsys.readouterr().out


def test_cifar_stream_matches_load(tmp_path, mesh):
    from keystone_tpu.loaders.cifar import RECORD, CifarLoader

    rng = np.random.default_rng(0)
    recs = rng.integers(0, 255, size=(37, RECORD)).astype(np.uint8)
    recs[:, 0] = rng.integers(0, 10, size=37)
    path = str(tmp_path / "batch.bin")
    recs.tofile(path)
    mem = CifarLoader.load(path)
    st = CifarLoader.stream(path, batch_size=8)
    assert st.data.n == 37
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())
    np.testing.assert_allclose(
        np.concatenate(list(st.data.batches())), mem.data.numpy()
    )


def test_imagenet_stream_undecodable_member_substitutes_zero(tmp_path, caplog):
    """An undecodable tar member must keep its label slot as a zero
    image (the index pass fixed the row/label alignment), with a
    warning — unlike load(), which may skip it."""
    import logging
    import tarfile

    root = _write_jpeg_tars(str(tmp_path / "tars"), num_tars=1, per_tar=3)
    tar = os.path.join(root, os.listdir(root)[0])
    with tarfile.open(tar, "a") as tf:
        bad = b"not a jpeg at all"
        info = tarfile.TarInfo(name="broken.JPEG")
        info.size = len(bad)
        tf.addfile(info, io.BytesIO(bad))
    st = ImageNetLoader.stream(root, size=(48, 48), batch_size=4)
    assert st.data.n == 4  # index counts all members
    with caplog.at_level(logging.WARNING, "keystone_tpu.loaders.imagenet"):
        imgs = np.concatenate(list(st.data.batches()))
    assert imgs.shape[0] == 4
    assert (imgs[-1] == 0).all()  # the broken member became a zero image
    assert any("undecodable" in r.message for r in caplog.records)


# ----------------------------------------------------- host text streams


def test_newsgroups_text_stream_matches_inmemory(tmp_path, mesh):
    """Host-stage text streaming: raw documents stream from disk through
    tokenize→n-gram→tf→vocab-fit→CSR→sparse solver without the corpus
    ever materializing; predictions must match the in-memory fit on the
    SAME training tree."""
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_accuracy import _write_newsgroups_fixture

    from keystone_tpu.loaders.newsgroups import NewsgroupsDataLoader
    from keystone_tpu.pipelines.newsgroups import Config, NewsgroupsPipeline

    train_root = _write_newsgroups_fixture(
        str(tmp_path / "train"), num_classes=3, docs_per_class=40, seed=0
    )
    test_root = _write_newsgroups_fixture(
        str(tmp_path / "test"), num_classes=3, docs_per_class=10, seed=1
    )
    out_stream = NewsgroupsPipeline.run(
        Config(
            data_path=train_root,
            test_path=test_root,
            head="ls",
            ls_lam=1e-2,
            num_features=16384,  # engages the real sparse route
            stream=True,
            stream_batch_size=16,
        )
    )
    # reference: in-memory fit on the SAME training tree, same test tree
    train = NewsgroupsDataLoader.load(train_root)
    test = NewsgroupsDataLoader.load(test_root)
    cfg = Config(head="ls", ls_lam=1e-2, num_features=16384, num_classes=3)
    fitted = NewsgroupsPipeline.build(cfg, train.data, train.labels).fit()
    preds = fitted(test.data).get().numpy().ravel()[: test.labels.n]
    acc_mem = float((preds == test.labels.numpy()).mean())
    assert abs(out_stream["accuracy"] - acc_mem) < 1e-6, (
        out_stream["accuracy"],
        acc_mem,
    )


def test_host_stream_never_materializes_through_featurizer(mesh):
    """The raw-text stream must stay lazy through the host transformer
    chain: only the featurized CSR rows may be collected."""
    from keystone_tpu.ops.nlp import (
        CommonSparseFeatures,
        LowerCase,
        Tokenizer,
    )

    reads = []

    def batches():
        for i in range(0, 30, 10):
            reads.append(i)
            yield [f"word{j} word{j} common" for j in range(i, i + 10)]

    ds = StreamDataset(batches, n=30, host=True)
    assert ds.is_host
    mapped = Tokenizer().apply_dataset(LowerCase().apply_dataset(ds))
    assert isinstance(mapped, StreamDataset) and mapped.is_host
    assert reads == []  # nothing consumed yet: lazy end to end
    csf = CommonSparseFeatures(8, sparse_output=True)
    from keystone_tpu.ops.nlp import TermFrequency, log_tf

    tf = TermFrequency(log_tf).apply_dataset(mapped)
    model = csf.fit_dataset(tf)  # ONE streaming df sweep
    assert reads == [0, 10, 20]
    rows_stream = model.apply_dataset(tf)
    assert isinstance(rows_stream, StreamDataset)
    rows = rows_stream.items  # CSR collection is the intended small sink
    assert len(rows) == 30 and hasattr(rows[0], "tocoo")


def test_newsgroups_text_stream_dense_nb_head(tmp_path, mesh):
    """Dense featurizer output (num_features < sparse threshold) over a
    text stream must become a DEVICE stream the NB head can consume
    (review finding: it used to dead-end as a host stream)."""
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_accuracy import _write_newsgroups_fixture

    from keystone_tpu.pipelines.newsgroups import Config, NewsgroupsPipeline

    train_root = _write_newsgroups_fixture(
        str(tmp_path / "train"), num_classes=3, docs_per_class=25, seed=0
    )
    test_root = _write_newsgroups_fixture(
        str(tmp_path / "test"), num_classes=3, docs_per_class=8, seed=1
    )
    out = NewsgroupsPipeline.run(
        Config(
            data_path=train_root,
            test_path=test_root,
            head="nb",
            num_features=512,  # dense route
            stream=True,
            stream_batch_size=16,
        )
    )
    assert out["accuracy"] > 0.5  # learnable; must not crash


def test_amazon_text_stream_matches_inmemory(tmp_path, mesh):
    """Amazon reviews: JSON-lines texts stream through HashingTF (host
    stream, no vocab fit needed) into the sparse logistic head; stream
    predictions match the in-memory fit on the same file."""
    import json as json_mod

    from keystone_tpu.loaders.amazon import AmazonReviewsDataLoader
    from keystone_tpu.pipelines.amazon_reviews import (
        AmazonReviewsPipeline,
        Config,
    )

    def write_jsonl(path, n, seed):
        data = AmazonReviewsDataLoader.synthetic(n, seed=seed)
        with open(path, "w") as f:
            for text, lab in zip(data.data.items, data.labels.numpy()):
                f.write(
                    json_mod.dumps(
                        {"reviewText": text, "overall": 5.0 if lab else 1.0}
                    )
                    + "\n"
                )
        return path

    train_path = write_jsonl(str(tmp_path / "train.jsonl"), 120, 1)
    test_path = write_jsonl(str(tmp_path / "test.jsonl"), 40, 2)
    out = AmazonReviewsPipeline.run(
        Config(
            data_path=train_path,
            test_path=test_path,
            stream=True,
            stream_batch_size=32,
            num_features=16384,
            num_iters=30,
        )
    )
    # reference: in-memory fit on the SAME file
    train = AmazonReviewsDataLoader.load(train_path)
    test = AmazonReviewsDataLoader.load(test_path)
    cfg = Config(num_features=16384, num_iters=30)
    fitted = AmazonReviewsPipeline.build(cfg, train.data, train.labels).fit()
    preds = fitted(test.data).get().numpy().ravel()[: test.labels.n]
    acc_mem = float((preds == test.labels.numpy()).mean())
    assert abs(out["accuracy"] - acc_mem) < 1e-6, (out["accuracy"], acc_mem)


def test_voc_stream_matches_load(tmp_path, mesh):
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_accuracy import _write_voc_fixture

    from keystone_tpu.loaders.voc import VOCLoader

    img_dir, ann_dir = _write_voc_fixture(str(tmp_path / "voc"), n=15)
    mem = VOCLoader.load(img_dir, ann_dir, size=(48, 48))
    st = VOCLoader.stream(img_dir, ann_dir, size=(48, 48), batch_size=4)
    assert st.data.n == mem.data.n == 15
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())
    np.testing.assert_array_equal(
        np.concatenate(list(st.data.batches())), mem.data.numpy()
    )

    # index-subset loads: rows/labels follow the subset, and the Dataset
    # NAMES are distinct per subset — names feed CSE/saved-state keys,
    # so train/test subsets of one directory must never alias
    idx = VOCLoader.index(img_dir, ann_dir)
    a = VOCLoader.load(img_dir, ann_dir, size=(48, 48), indices=[0, 2, 4], index=idx)
    b = VOCLoader.load(img_dir, ann_dir, size=(48, 48), indices=[1, 3], index=idx)
    np.testing.assert_array_equal(a.data.numpy(), mem.data.numpy()[[0, 2, 4]])
    np.testing.assert_array_equal(b.labels.numpy(), mem.labels.numpy()[[1, 3]])
    assert a.data.name != b.data.name != mem.data.name
    sa = VOCLoader.stream(
        img_dir, ann_dir, size=(48, 48), batch_size=2, indices=[0, 2, 4], index=idx
    )
    np.testing.assert_array_equal(
        np.concatenate(list(sa.data.batches())), mem.data.numpy()[[0, 2, 4]]
    )


def test_mnist_app_stream_matches_inmemory(tmp_path, mesh):
    """MnistRandomFFT --stream: CSV rows re-parse per sweep; the exact
    solver's streaming sufficient statistics must reproduce the
    in-memory fit through the app entry point."""
    from keystone_tpu.loaders.mnist import MnistLoader
    from keystone_tpu.pipelines.mnist_random_fft import Config, MnistRandomFFT

    # write a small CSV in the app's format (label, 784 pixels)
    synth = MnistLoader.synthetic(192, seed=3)
    mat = np.column_stack(
        [synth.labels.numpy().astype(np.float32), synth.data.numpy()]
    )
    train_csv = str(tmp_path / "train.csv")
    np.savetxt(train_csv, mat, delimiter=",", fmt="%.4f")
    test_synth = MnistLoader.synthetic(64, seed=4)
    test_csv = str(tmp_path / "test.csv")
    np.savetxt(
        test_csv,
        np.column_stack(
            [test_synth.labels.numpy().astype(np.float32), test_synth.data.numpy()]
        ),
        delimiter=",",
        fmt="%.4f",
    )
    base = dict(
        train_path=train_csv, test_path=test_csv, num_ffts=2, lam=1e-2
    )
    out_stream = MnistRandomFFT.run(
        Config(**base, stream=True, stream_batch_size=48)
    )
    out_mem = MnistRandomFFT.run(Config(**base))
    assert abs(out_stream["accuracy"] - out_mem["accuracy"]) < 0.02, (
        out_stream["accuracy"],
        out_mem["accuracy"],
    )


def test_timit_stream_csv_features(tmp_path, mesh):
    """TIMIT stream's CSV branch (the npy branch is covered above)."""
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(25, 6)).astype(np.float32)
    labs = rng.integers(0, 4, size=25)
    fp, lp = str(tmp_path / "f.csv"), str(tmp_path / "l.txt")
    np.savetxt(fp, feats, delimiter=",", fmt="%.6f")
    np.savetxt(lp, labs, fmt="%d")
    mem = TimitFeaturesDataLoader.load(fp, lp)
    st = TimitFeaturesDataLoader.stream(fp, lp, batch_size=7)
    np.testing.assert_array_equal(st.labels.numpy(), mem.labels.numpy())
    np.testing.assert_allclose(
        np.concatenate(list(st.data.batches())), mem.data.numpy(), rtol=1e-5
    )


def test_linear_pixels_app_stream_matches_inmemory(tmp_path, mesh):
    """LinearPixels --stream: CIFAR records re-read per sweep through
    ImageVectorizer into the exact solver's streaming fit."""
    from keystone_tpu.loaders.cifar import RECORD
    from keystone_tpu.pipelines.linear_pixels import Config, LinearPixels

    def write_records(path, n, seed):
        r = np.random.default_rng(seed)
        recs = r.integers(0, 255, size=(n, RECORD)).astype(np.uint8)
        recs[:, 0] = r.integers(0, 10, size=n)
        # class-dependent brightness so the baseline is learnable
        recs[:, 1:] = np.clip(
            recs[:, 1:] // 4 + recs[:, :1] * 20, 0, 255
        ).astype(np.uint8)
        recs.tofile(path)
        return path

    train_bin = write_records(str(tmp_path / "train.bin"), 160, 1)
    test_bin = write_records(str(tmp_path / "test.bin"), 48, 2)
    base = dict(train_path=train_bin, test_path=test_bin, lam=1e-3)
    out_stream = LinearPixels.run(
        Config(**base, stream=True, stream_batch_size=32)
    )
    out_mem = LinearPixels.run(Config(**base))
    assert abs(out_stream["accuracy"] - out_mem["accuracy"]) < 0.03, (
        out_stream["accuracy"],
        out_mem["accuracy"],
    )
    # --stream without --test-path must refuse rather than eagerly load
    with pytest.raises(ValueError, match="test-path"):
        LinearPixels.run(Config(train_path=train_bin, stream=True))
