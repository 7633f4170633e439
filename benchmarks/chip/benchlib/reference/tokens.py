"""Synthetic non-iid token shards, one bigram skeleton per UAV.

Same construction as the program's seeded data contract: a stream seeded
by ``[seed, u]`` draws a permutation of the vocabulary (each token's
successor), first tokens from a Zipf unigram law (p(v) ~ 1/(v+1)), and
each next token is the successor with probability 0.7, else a fresh Zipf
draw.  UAV u's shard of ``n_u`` sequences indexes a pool of ``pool``
sequences of its stream (sample i is pool row i % pool); one more row of
the streams of UAVs ``j * n_uavs // eval_seqs`` is the held-out set.
The sample counts are the digits' non-iid split: the labels are the
first draw of the digits' stream."""
from __future__ import annotations

import numpy as np

from . import digits


def stream(n_seqs: int, seq_len: int, vocab: int, seed):
    """(n_seqs, seq_len + 1) int64 tokens."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1)
    cdf = np.cumsum(p / p.sum())
    cdf /= cdf[-1]

    def zipf(n):
        return cdf.searchsorted(rng.random(n), side="right")

    succ = rng.permutation(vocab)
    toks = np.empty((n_seqs, seq_len + 1), np.int64)
    toks[:, 0] = zipf(n_seqs)
    for t in range(seq_len):
        follow = rng.random(n_seqs) < 0.7
        toks[:, t + 1] = np.where(follow, succ[toks[:, t]], zipf(n_seqs))
    return toks


def sample_counts(cfg: dict, seed: int):
    n = cfg["n_train"]
    y = np.random.default_rng(seed).integers(0, 10, n + cfg["n_test"])[:n]
    return [len(i) for i in digits.split_noniid(y, cfg["n_uavs"], seed)]


def environment(cfg: dict, seed: int):
    """(per-UAV sample counts, per-UAV pools (pool, S + 1) int32,
    held-out (eval_seqs, S + 1) int32)."""
    if cfg["distribution"] != "noniid":
        raise ValueError("the reference implements the non-iid split only")
    n, pool = cfg["n_uavs"], cfg["token_pool"]
    pools = [stream(pool + 1, cfg["seq_len"], cfg["vocab_size"], [seed, u])
             .astype(np.int32) for u in range(n)]
    held = np.stack([pools[j * n // cfg["eval_seqs"]][pool]
                     for j in range(cfg["eval_seqs"])])
    return sample_counts(cfg, seed), [p[:pool] for p in pools], held
