"""Synthetic datasets (offline container: no MNIST download).

``make_digits`` builds an MNIST-shaped 10-class image problem whose classes
are deterministic smoothed prototype blobs + per-sample jitter/noise — a
5-layer CNN separates it well but not trivially (accuracy climbs over tens of
FL rounds, which is what the paper's figures need).  ``make_token_stream``
builds LM token data with Zipfian unigrams + Markov bigram structure for the
framework examples.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Dataset:
    x: np.ndarray     # images (N, 28, 28, 1) float32 or tokens (N, S) int32
    y: np.ndarray     # labels (N,) or next-token targets (N, S)

    def __len__(self) -> int:
        return len(self.x)


def _smooth(img: np.ndarray, iters: int = 2) -> np.ndarray:
    for _ in range(iters):
        img = (img
               + np.roll(img, 1, 0) + np.roll(img, -1, 0)
               + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
    return img


def make_digits(n: int, seed: int = 0, side: int = 28,
                num_classes: int = 10, noise: float = 0.8) -> Dataset:
    rng = np.random.default_rng(seed)
    protos = []
    proto_rng = np.random.default_rng(1234)      # class shapes fixed across sims
    for _ in range(num_classes):
        base = (proto_rng.random((side, side)) < 0.18).astype(np.float32)
        protos.append(_smooth(base, 4) * 3.0)
    protos = np.stack(protos)                    # (C, side, side)

    y = rng.integers(0, num_classes, n)
    shifts = rng.integers(-3, 4, (n, 2))
    xs = np.empty((n, side, side, 1), np.float32)
    for i in range(n):
        img = np.roll(protos[y[i]], tuple(shifts[i]), (0, 1))
        img = img + rng.standard_normal((side, side)).astype(np.float32) * noise
        xs[i, :, :, 0] = img
    mean, std = xs.mean(), xs.std() + 1e-6
    return Dataset(((xs - mean) / std).astype(np.float32), y.astype(np.int32))


def make_token_stream(n_seqs: int, seq_len: int, vocab: int,
                      seed=0) -> Dataset:
    """Zipf unigram + noisy-successor bigram LM data."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    # rng.choice(vocab, n, p=probs), with the cdf built once
    cdf = probs.cumsum()
    cdf /= cdf[-1]

    def zipf(n):
        return cdf.searchsorted(rng.random(n), side="right")

    succ = rng.permutation(vocab)                # deterministic bigram skeleton
    toks = np.empty((n_seqs, seq_len + 1), np.int64)
    toks[:, 0] = zipf(n_seqs)
    for t in range(seq_len):
        follow = rng.random(n_seqs) < 0.7
        toks[:, t + 1] = np.where(follow, succ[toks[:, t]], zipf(n_seqs))
    return Dataset(toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32))


class _PoolRows:
    """``n`` samples backed by a smaller pool: sample i is row i % len(rows)."""

    def __init__(self, rows: np.ndarray, n: int) -> None:
        self.rows, self.shape, self.dtype = rows, (n,) + rows.shape[1:], \
            rows.dtype

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, idx):
        return self.rows[np.asarray(idx) % len(self.rows)]


def make_token_shards(sizes, pool: int, seq_len: int, vocab: int, seed,
                      eval_seqs: int):
    """Non-iid LM shards, one bigram skeleton per client.

    Client u holds ``sizes[u]`` sequences of ``seq_len`` tokens, which
    index a pool of ``pool`` sequences of its own stream
    (``make_token_stream`` seeded by ``[seed, u]``), so that the host never
    holds the whole corpus.  Each stream draws one row more; the held-out
    set is that row of clients ``j * n // eval_seqs``, j < ``eval_seqs``.
    Returns (the clients' ``Dataset``s, held-out ``Dataset``)."""
    n = len(sizes)
    streams = [make_token_stream(pool + 1, seq_len, vocab, seed=[seed, u])
               for u in range(n)]
    clients = [Dataset(_PoolRows(s.x[:pool], k), _PoolRows(s.y[:pool], k))
               for s, k in zip(streams, sizes)]
    held = [streams[j * n // eval_seqs] for j in range(eval_seqs)]
    test = Dataset(np.stack([s.x[pool] for s in held]),
                   np.stack([s.y[pool] for s in held]))
    return clients, test
