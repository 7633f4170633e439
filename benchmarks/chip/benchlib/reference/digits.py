"""Synthetic MNIST-shaped digits and the non-iid client split.

Same construction as the program's seeded data contract: ten fixed
prototype blobs (shapes seeded by 1234, shared by every simulation),
per-sample shift and Gaussian noise from the simulation seed, global
standardisation.  The non-iid split gives every client two classes in
label-sorted shards (McMahan et al., the paper's [9])."""
from __future__ import annotations

import numpy as np


def _smooth(img: np.ndarray, iters: int) -> np.ndarray:
    for _ in range(iters):
        img = (img + np.roll(img, 1, 0) + np.roll(img, -1, 0)
               + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
    return img


def digits(n: int, seed: int, side: int = 28, classes: int = 10,
           noise: float = 0.8):
    """(x (n, side, side, 1) float32, y (n,) int32)."""
    rng = np.random.default_rng(seed)
    proto_rng = np.random.default_rng(1234)
    protos = np.stack([
        _smooth((proto_rng.random((side, side)) < 0.18).astype(np.float32),
                4) * 3.0
        for _ in range(classes)])
    y = rng.integers(0, classes, n)
    shifts = rng.integers(-3, 4, (n, 2))
    xs = np.empty((n, side, side, 1), np.float32)
    for i in range(n):
        img = np.roll(protos[y[i]], tuple(shifts[i]), (0, 1))
        img = img + rng.standard_normal((side, side)).astype(np.float32) \
            * noise
        xs[i, :, :, 0] = img
    mean, std = xs.mean(), xs.std() + 1e-6
    return ((xs - mean) / std).astype(np.float32), y.astype(np.int32)


def split_noniid(y: np.ndarray, n_clients: int, seed: int,
                 per_client: int = 2):
    """Index arrays, one per client, each holding ``per_client`` classes."""
    rng = np.random.default_rng(seed)
    classes = np.unique(y)
    pools = {c: rng.permutation(np.where(y == c)[0]) for c in classes}
    picks = [[classes[(i * per_client + j) % len(classes)]
              for j in range(per_client)] for i in range(n_clients)]
    rng.shuffle(picks)
    uses = {c: sum(c in row for row in picks) for c in classes}
    cursor = {c: 0 for c in classes}
    out = []
    for row in picks:
        idx = []
        for c in row:
            share = len(pools[c]) // max(uses[c], 1)
            idx.append(pools[c][cursor[c]:cursor[c] + share])
            cursor[c] += share
        out.append(np.concatenate(idx))
    return out


def environment(cfg: dict, seed: int):
    """Train shards and test set of one simulation, as index-free arrays."""
    if cfg["distribution"] != "noniid":
        raise ValueError(f"the reference implements the non-iid split only, "
                         f"not {cfg['distribution']!r}")
    x, y = digits(cfg["n_train"] + cfg["n_test"], seed)
    n = cfg["n_train"]
    shards = split_noniid(y[:n], cfg["n_uavs"], seed)
    clients = [(x[:n][i], y[:n][i]) for i in shards]
    return clients, (x[n:], y[n:])
