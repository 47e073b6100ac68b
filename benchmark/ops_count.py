"""Operations and bytes of the algorithms, from their shapes alone
(2 x multiply-accumulates; copied from ``bench.py § flops_per_image,
solver_flops``, PR 21 tree, with the bytes added).  They count what the
ALGORITHM needs, whatever implements it, so a later PR that replaces a
kernel is read against the same work.  Elementwise work is left out.
"""

from __future__ import annotations


def solver_flops(n: int, d: int, k: int, block: int, epochs: int) -> float:
    """Weighted block coordinate descent: per epoch and block of width w,
    the Gramian (2 n w^2), the cross term (2 n w k), the target and the
    residual update (4 n w k together), and the w^3/3 Cholesky with its
    two triangular solves (2 w^2 k)."""
    per_epoch = 0.0
    for lo in range(0, d, block):
        w = min(block, d - lo)
        per_epoch += 2.0 * n * w * w + 6.0 * n * w * k + w**3 / 3.0 + 2.0 * w * w * k
    return float(epochs * per_epoch)


def solver_bytes(n: int, d: int, k: int, block: int, epochs: int) -> float:
    """Least float32 traffic of the same sweep: each block of the feature
    matrix read once per epoch, labels and residual read and the residual
    written once per block step, the block's Gramian and weights written."""
    per_epoch = 0.0
    for lo in range(0, d, block):
        w = min(block, d - lo)
        per_epoch += 4.0 * (n * w + 3.0 * n * k + w * w + 2.0 * w * k)
    return float(epochs * per_epoch)


def cosine_features_flops(n: int, d_in: int, d_out: int) -> float:
    """x W^T for n rows: 2 n d_in d_out (the cosine is elementwise)."""
    return 2.0 * n * d_in * d_out


def cosine_features_bytes(n: int, d_in: int, d_out: int) -> float:
    return 4.0 * (n * d_in + d_in * d_out + n * d_out)


def fv_kernel_per_image(descriptors: int, d_in: int, pca: int, gmm_k: int) -> dict:
    """One image through one branch's PCA + Fisher-vector encode: the
    projection (2 T d_in D) and four T x D x K contractions (the two of the
    posterior's quadratic form, the two sufficient statistics); its least
    traffic is the descriptors in (bf16 as the policy streams them), the
    vocabulary, and the 2 K D vector out."""
    flops = 2.0 * descriptors * d_in * pca + 8.0 * descriptors * pca * gmm_k
    nbytes = 2.0 * descriptors * d_in + 4.0 * (d_in * pca + 2 * gmm_k * pca) + 4.0 * 2 * gmm_k * pca
    return {"flops": flops, "bytes": nbytes}


def sift_lcs_fv_per_image(
    image: int, sift_step: int, sift_bin: int, lcs_sub: int, pca: int, gmm_k: int,
    classes: int, t_sift: int, t_lcs: int,
) -> dict:
    """One image through both branches of ImageNetSiftLcsFV and the scoring of
    its 2 x 2 K D features (``bench.py § flops_per_image``, with the LCS
    branch added): the SIFT windowing as two dense contractions with
    P = 4 x centers rows, the blur as two banded contractions, each branch's
    PCA + FV, and the block-linear scoring.  Elementwise work and the LCS box
    sums are left out."""
    centers = len(range(2 * sift_bin, image - 2 * sift_bin, sift_step))
    p = 4 * centers
    sift = 2.0 * p * image * image * 8 + 2.0 * p * image * p * 8 + 4.0 * image**3
    fv_sift = fv_kernel_per_image(t_sift, 128, pca, gmm_k)
    fv_lcs = fv_kernel_per_image(t_lcs, 96, pca, gmm_k)
    features = 2 * 2 * gmm_k * pca
    scoring = 2.0 * features * classes
    return {
        "featurize_flops": sift + fv_sift["flops"] + fv_lcs["flops"],
        "scoring_flops": scoring,
        "fv_kernel_flops": fv_sift["flops"] + fv_lcs["flops"],
        "fv_kernel_bytes": fv_sift["bytes"] + fv_lcs["bytes"],
        "image_bytes": 3.0 * image * image,
    }


def roofline_seconds(flops: float, nbytes: float, peaks: dict, chips: int) -> tuple:
    """The least time ``chips`` chips could take, and which peak bounds it."""
    by_flops = flops / (peaks["bf16_flops_per_s"] * chips)
    by_bytes = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes else "bytes")
