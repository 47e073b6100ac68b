import numpy as np

from keystone_tpu.evaluation import (
    AugmentedExamplesEvaluator,
    BinaryClassifierEvaluator,
    MeanAveragePrecisionEvaluator,
    MulticlassClassifierEvaluator,
)


def test_multiclass_evaluator():
    preds = np.array([0, 1, 2, 1, 0])
    labels = np.array([0, 1, 1, 1, 2])
    m = MulticlassClassifierEvaluator(3).evaluate(preds, labels)
    assert abs(m.accuracy - 3 / 5) < 1e-9
    assert m.confusion_matrix.sum() == 5
    assert m.confusion_matrix[1, 1] == 2  # actual 1 predicted 1
    assert m.confusion_matrix[1, 2] == 1  # actual 1 predicted 2
    assert 0 <= m.macro_f1 <= 1


def test_binary_evaluator():
    preds = np.array([1, 1, 0, 0, 1])
    labels = np.array([1, 0, 0, 1, 1])
    m = BinaryClassifierEvaluator().evaluate(preds, labels)
    assert m.tp == 2 and m.fp == 1 and m.tn == 1 and m.fn == 1
    assert abs(m.precision - 2 / 3) < 1e-9
    assert abs(m.recall - 2 / 3) < 1e-9


def test_map_evaluator_perfect_ranking():
    scores = np.array([[0.9, 0.1], [0.8, 0.2], [0.1, 0.9], [0.2, 0.8]])
    labels = np.array([[1, 0], [1, 0], [0, 1], [0, 1]])
    ap = MeanAveragePrecisionEvaluator(2).evaluate(scores, labels)
    assert abs(ap - 1.0) < 1e-9


def test_map_evaluator_partial():
    scores = np.array([[0.9], [0.8], [0.7]])
    labels = np.array([[0], [1], [1]])
    # ranking: doc0 (neg), doc1 (pos, P=1/2), doc2 (pos, P=2/3)
    ap = MeanAveragePrecisionEvaluator(1).evaluate(scores, labels)
    assert abs(ap - (0.5 + 2 / 3) / 2) < 1e-9


def test_augmented_examples_evaluator():
    # two images, two views each; views disagree, average decides
    scores = np.array(
        [[0.9, 0.1], [0.2, 0.8], [0.1, 0.9], [0.4, 0.6]], np.float64
    )
    ids = np.array([7, 7, 3, 3])
    labels_per_image = np.array([1, 1])  # uniq order: [3, 7]
    m = AugmentedExamplesEvaluator(2).evaluate(scores, ids, labels_per_image)
    # image 3: mean [0.25, 0.75] → 1 ✓; image 7: mean [0.55, 0.45] → 0 ✗
    assert abs(m.accuracy - 0.5) < 1e-9


def test_augmented_examples_evaluator_unsorted_ids():
    # ids occur as img9 first, img1 second; labels in occurrence order
    scores = np.array([[1.0, 0.0], [1.0, 0.0], [0.0, 1.0], [0.0, 1.0]])
    ids = np.array([9, 9, 1, 1])
    labels_occurrence_order = np.array([0, 1])  # img9 -> 0, img1 -> 1
    m = AugmentedExamplesEvaluator(2).evaluate(scores, ids, labels_occurrence_order)
    assert m.accuracy == 1.0


def test_map_evaluator_hand_computed_multiclass():
    """Hand-computed 3-class fixture (round-2 review item 6).

    Class 0, score order d0>d2>d1, labels [1,0,1]:
      rank1 d0 pos P=1/1; rank2 d2 neg; rank3 d1 pos P=2/3
      AP0 = (1 + 2/3)/2 = 5/6
    Class 1, order d1>d0>d3, labels (by doc) d1=0, d0=1, d3=1:
      rank2 d0 pos P=1/2; rank3 d3 pos P=2/3 -> AP1 = (1/2+2/3)/2 = 7/12
    Class 2, order d3>d2, labels d3=1, d2=0, d0/d1 scored lowest (neg):
      rank1 d3 pos P=1 -> AP2 = 1
    mAP = (5/6 + 7/12 + 1)/3 = 29/36
    """
    scores = np.array(
        [
            # class0 class1 class2
            [0.9, 0.5, 0.05],  # d0
            [0.2, 0.8, 0.01],  # d1
            [0.5, 0.0, 0.30],  # d2
            [0.1, 0.4, 0.90],  # d3
        ]
    )
    labels = np.array(
        [
            [1, 1, 0],
            [1, 0, 0],
            [0, 0, 0],
            [0, 1, 1],
        ]
    )
    ap = MeanAveragePrecisionEvaluator(3).evaluate(scores, labels)
    assert abs(ap - 29 / 36) < 1e-9, ap
