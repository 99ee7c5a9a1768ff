"""Seeded traffic generators, read from a traffic file's parameters.

`lm_task_batches` follows the program's LM task generator
(`data/lm_tasks.make_lm_task_batch`): each client speaks a dialect, a
permutation of a random slice of the vocabulary, over an order-1 chain
that either steps to the next token id or jumps to a uniform one. It is
written here, vectorized, so that a change to the program's data code
cannot move the benchmark's traffic.
"""
from __future__ import annotations

import numpy as np


def dialect_sequences(rng: np.random.Generator, n_seqs: int, length: int,
                      vocab: int, dialect_frac: float,
                      stay_prob: float) -> np.ndarray:
    """(n_seqs, length) int32 token ids of one client's dialect."""
    perm = np.arange(vocab)
    sl = rng.choice(vocab, size=max(2, int(vocab * dialect_frac)),
                    replace=False)
    perm[sl] = rng.permutation(sl)
    jumps = rng.integers(0, vocab, size=(n_seqs, length))
    stay = rng.random((n_seqs, length)) < stay_prob
    stay[:, 0] = False
    pos = np.arange(length)
    # the last jump at or before each position, and the steps since it
    last = np.maximum.accumulate(np.where(stay, 0, pos), axis=1)
    stream = (np.take_along_axis(jumps, last, axis=1) + (pos - last)) % vocab
    return perm[stream].astype(np.int32)


def lm_task_batches(rng: np.random.Generator, traffic: dict,
                    vocab: int) -> list[dict]:
    """`traffic["distinct_batches"]` task batches, each
    {"support": (clients, support_seqs, seq_len), "query": (clients,
    query_seqs, seq_len)} int32; every row differs from every other."""
    C, L = traffic["clients"], traffic["seq_len"]
    s, q = traffic["support_seqs"], traffic["query_seqs"]
    out = []
    for _ in range(traffic["distinct_batches"]):
        sup = np.empty((C, s, L), np.int32)
        qry = np.empty((C, q, L), np.int32)
        for c in range(C):
            seqs = dialect_sequences(rng, s + q, L, vocab,
                                     traffic["dialect_frac"],
                                     traffic["stay_prob"])
            sup[c], qry[c] = seqs[:s], seqs[s:]
        out.append({"support": sup, "query": qry})
    return out


# ------------------------------------------------------------ FEMNIST

def _class_prototypes(rng: np.random.Generator, num_classes: int,
                      size: int) -> np.ndarray:
    """Smooth random patterns, one per class: low-frequency blobs."""
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    protos = np.zeros((num_classes, size, size), np.float32)
    for c in range(num_classes):
        img = np.zeros((size, size), np.float32)
        for _ in range(4):
            fx, fy = rng.uniform(1, 4, size=2)
            px, py = rng.uniform(0, 2 * np.pi, size=2)
            amp = rng.uniform(0.5, 1.0)
            img += amp * np.sin(2 * np.pi * fx * xx + px) * \
                np.sin(2 * np.pi * fy * yy + py)
        protos[c] = (img - img.min()) / (np.ptp(img) + 1e-6)
    return protos


def _affine_warp(imgs: np.ndarray, theta: float, shear: float,
                 scale: float) -> np.ndarray:
    """Nearest-neighbour affine warp of (k, H, W) about the centre."""
    size = imgs.shape[-1]
    c = (size - 1) / 2.0
    ct, st = np.cos(theta), np.sin(theta)
    a = np.array([[ct, -st + shear], [st, ct]], np.float32) / scale
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    ys = a[0, 0] * (yy - c) + a[0, 1] * (xx - c) + c
    xs = a[1, 0] * (yy - c) + a[1, 1] * (xx - c) + c
    ys = np.clip(np.round(ys).astype(int), 0, size - 1)
    xs = np.clip(np.round(xs).astype(int), 0, size - 1)
    return imgs[:, ys, xs]


def femnist_writers(rng: np.random.Generator, traffic: dict,
                    num_classes: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Synthetic FEMNIST writers, following the program's generator
    (`data/synth_femnist.py`): per-class prototypes; per writer a fixed
    warp, contrast and bias, a skewed class subset and a lognormal sample
    count. -> [(x (n, H, W) float32 in [0, 1], y (n,) int32)]."""
    size, mean = traffic["image_size"], traffic["mean_samples"]
    protos = _class_prototypes(rng, num_classes, size)
    writers = []
    for _ in range(traffic["train_writers"]):
        theta, shear = rng.uniform(-0.5, 0.5), rng.uniform(-0.3, 0.3)
        scale, contrast = rng.uniform(0.8, 1.2), rng.uniform(0.7, 1.3)
        bias = rng.uniform(-0.1, 0.1)
        k = int(rng.integers(max(2, num_classes // 7), num_classes + 1))
        classes = rng.choice(num_classes, size=k, replace=False)
        pvals = rng.dirichlet(np.ones(k) * 0.5)
        n = int(np.clip(rng.lognormal(np.log(mean), 0.4), 8, 4 * mean))
        ys = classes[rng.choice(k, size=n, p=pvals)]
        warped = _affine_warp(protos, theta, shear, scale)
        xs = np.clip(contrast * warped[ys] + bias
                     + rng.normal(0, 0.15, (n, size, size)), 0, 1)
        writers.append((xs.astype(np.float32), ys.astype(np.int32)))
    return writers
