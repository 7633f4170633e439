"""Block absmax snapshot codec: a user's delta against the round-start
global model, flattened leaf by leaf in the pytree's key order, padded to
whole ``block``-wide rows (row count rounded up to the codec's 256-row
tile), quantized per row to +-(2^(bits-1) - 1) steps of absmax/qmax with
round-half-even, and restored as q * scale."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TILE_ROWS = 256


def roundtrip(params, base, block: int, bits: int):
    """What the server holds of ``params`` after the snapshot's trip."""
    leaves, tdef = jax.tree_util.tree_flatten(params)
    base_leaves = jax.tree_util.tree_leaves(base)
    flat = jnp.concatenate([(a - b).ravel() for a, b in
                            zip(leaves, base_leaves)]).astype(jnp.float32)
    n = flat.size
    rows = math.ceil(max(1, math.ceil(n / block)) / TILE_ROWS) * TILE_ROWS
    x = jnp.pad(flat, (0, rows * block - n)).reshape(rows, block)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax,
                        1e-12)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    delta = (q * scale).reshape(-1)[:n]
    out, off = [], 0
    for a, b in zip(leaves, base_leaves):
        out.append(b + delta[off:off + a.size].reshape(a.shape))
        off += a.size
    return jax.tree_util.tree_unflatten(tdef, out)
