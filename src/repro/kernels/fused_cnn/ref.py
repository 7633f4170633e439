"""Pure-jnp oracle for the fused CNN training step (pool-first layout).

The paper's hot loop is K users x e epochs x S steps of the 5-layer MNIST
CNN — stock XLA autodiff of ``cnn.forward_im2col`` pays full-resolution
bias/ReLU passes and re-derives the pool/ReLU selection masks in the
backward.  This module is the algorithmic reference the Pallas kernels
(``kernel.py``) and the XLA fast path (``ops.py``) are pinned against:

- **pool-first conv block**: ``pool(relu(z + b)) == relu(pool(z) + b)``
  *bit-for-bit* (max commutes with the monotone per-channel bias add, and
  relu is monotone), so the bias add and ReLU run at pooled resolution —
  4x fewer elements than the ``forward_im2col`` order.  Forward values
  are identical to ``cnn.forward_im2col`` at f32.
- **hand-written backward**: the forward saves the im2col patch matrix,
  the pool argmax mask ``eq = (z == pooled_z)`` (with JAX's tie-splitting
  1/count semantics, so grads match ``jax.grad`` of the reference
  exactly) and the ReLU mask — the backward is pure mask algebra plus the
  two transposed matmuls, never re-deriving activations.
- **input gradient** (conv2's ``dx``, which conv1's backward needs): the
  patch cotangent folds back with ``fold3x3``, a sum of nine shifted
  slices of it, which XLA fuses into one pass (no scatter-add).
- conv1's ``dx`` (the fold back to the input image) is exposed but unused
  by the training step — images carry no gradient, XLA DCEs it.

``D`` below is the compute dtype (f32, or bf16 under the mixed-precision
policy); matmul accumulation is always f32 (``preferred_element_type``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _dot(a, b):
    """Matmul with f32 accumulation, result in the compute dtype."""
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def patches3x3(x: jnp.ndarray) -> jnp.ndarray:
    """(B, H, W, C) -> (B, H, W, 9C) SAME-padded 3x3 patch view, in the
    per-pixel contraction order of ``conv_general_dilated``.

    Delegates to ``cnn._patches3x3`` — one copy of the patch-ordering
    contract the bit-equivalence pin against ``forward_im2col`` rests on
    (``kernel.py`` necessarily re-states it inside the Pallas program)."""
    from repro.models.cnn import _patches3x3
    return _patches3x3(x)


def fold3x3(dpatches: jnp.ndarray) -> jnp.ndarray:
    """Transpose of ``patches3x3``: fold (B,H,W,9C) -> (B,H,W,C).

    Written as a gather: pad the patch cotangent by 1 on each side of H
    and W, then add nine static slices in tap order t = 3i + j, the slice
    of tap t shifted by (2 - i, 2 - j).  Each element sums the same terms
    in the same order as a scatter-add of the taps into a zero buffer
    (a tap that misses the element adds an exact 0.0), so the values are
    the same bit for bit wherever each add rounds (f32; bf16 on the CPU,
    while a TPU fusion keeps a bf16 sum in f32 and rounds once).  XLA
    fuses the gather into one elementwise pass, where it runs a
    scatter-add as nine padded ``dynamic-update-slice`` ops on a TPU."""
    _, h, w, c9 = dpatches.shape
    c = c9 // 9
    dp = jnp.pad(dpatches, ((0, 0), (1, 1), (1, 1), (0, 0)))
    dx = None
    for t in range(9):
        i, j = divmod(t, 3)
        tap = dp[:, 2 - i:2 - i + h, 2 - j:2 - j + w, t * c:(t + 1) * c]
        dx = tap if dx is None else dx + tap
    return dx


def conv_pool_fwd(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, Tuple]:
    """Fused im2col conv + bias + ReLU + 2x2 maxpool, pool-first.

    x (B,H,W,C); w (3,3,C,O); b (O,).  Returns the block activation
    ``a (B,H/2,W/2,O)`` and residuals ``(pat, eq, relu_m)``:

      pat    (B·H·W, 9C)   — im2col patches (reused for dW)
      eq     (B,H,W,O)     — pool argmax mask, 1/count-weighted at ties
                             (exactly ``jax.grad``'s reduce-max rule)
      relu_m (B,H/2,W/2,O) — ReLU mask at pooled resolution

    The forward value equals ``cnn._conv_im2col`` bit-for-bit at f32:
    ``pool(relu(z+b)) == relu(pool(z)+b)`` because the per-channel bias
    add is monotone (the same window element wins the max) and relu is
    monotone.
    """
    bs, h, wd, c = x.shape
    o = w.shape[-1]
    pat = patches3x3(x).reshape(bs * h * wd, 9 * c)
    z = _dot(pat, w.reshape(9 * c, o)).reshape(bs, h, wd, o)
    zw = z.reshape(bs, h // 2, 2, wd // 2, 2, o)
    pz = zw.max(axis=(2, 4))
    a = jnp.maximum(pz + b, 0.0)
    eqw = (zw == pz[:, :, None, :, None, :])
    cnt = eqw.sum(axis=(2, 4), keepdims=True)
    eq = jnp.where(eqw, 1.0 / cnt, 0.0).astype(x.dtype).reshape(bs, h, wd, o)
    relu_m = (pz + b > 0).astype(x.dtype)
    return a, (pat, eq, relu_m)


def conv_pool_bwd(res: Tuple, w: jnp.ndarray, da: jnp.ndarray,
                  need_dx: bool) -> Tuple:
    """Backward of ``conv_pool_fwd`` from the saved masks.

    da (B,H/2,W/2,O) -> (dw (3,3,C,O), db (O,), dx (B,H,W,C) or None).
    ``db`` is summed at pooled resolution (4x cheaper than the im2col
    order, identical value: the bias reaches the loss only through the
    pool winners)."""
    pat, eq, relu_m = res
    bs, h, wd, o = eq.shape
    c = pat.shape[-1] // 9
    dp = da * relu_m                               # (B,H/2,W/2,O)
    db = dp.astype(jnp.float32).sum(axis=(0, 1, 2))
    dz = (eq.reshape(bs, h // 2, 2, wd // 2, 2, o)
          * dp[:, :, None, :, None, :]).reshape(bs * h * wd, o)
    dw = jax.lax.dot(pat.T, dz, preferred_element_type=jnp.float32)
    dw = dw.reshape(3, 3, c, o)
    dx = None
    if need_dx:
        dpat = _dot(dz, w.reshape(9 * c, o).T).reshape(bs, h, wd, 9 * c)
        dx = fold3x3(dpat)
    return dw, db, dx


def fc_chain_fwd(flat: jnp.ndarray, params: dict) -> Tuple[jnp.ndarray, Tuple]:
    """fc1+ReLU -> fc2+ReLU -> fc3 logits in one pass.

    flat (B, F).  Returns logits (B, num_classes) and residuals
    (h1, h2) — the ReLU masks are recovered as ``h > 0`` (free)."""
    h1 = jnp.maximum(_dot(flat, params["fc1"]["w"]) + params["fc1"]["b"], 0.0)
    h2 = jnp.maximum(_dot(h1, params["fc2"]["w"]) + params["fc2"]["b"], 0.0)
    logits = _dot(h2, params["fc3"]["w"]) + params["fc3"]["b"]
    return logits, (h1, h2)


def fc_chain_bwd(flat: jnp.ndarray, res: Tuple, params: dict,
                 dlogits: jnp.ndarray) -> Tuple[dict, jnp.ndarray]:
    """Backward of ``fc_chain_fwd``: grads for fc1..fc3 plus dflat."""
    h1, h2 = res

    def dot32(a, b):
        return jax.lax.dot(a, b, preferred_element_type=jnp.float32)

    g3 = {"w": dot32(h2.T, dlogits), "b": dlogits.astype(jnp.float32).sum(0)}
    dh2 = _dot(dlogits, params["fc3"]["w"].T) * (h2 > 0)
    g2 = {"w": dot32(h1.T, dh2), "b": dh2.astype(jnp.float32).sum(0)}
    dh1 = _dot(dh2, params["fc2"]["w"].T) * (h1 > 0)
    g1 = {"w": dot32(flat.T, dh1), "b": dh1.astype(jnp.float32).sum(0)}
    dflat = _dot(dh1, params["fc1"]["w"].T)
    return {"fc1": g1, "fc2": g2, "fc3": g3}, dflat


# ---------------------------------------------------------------------------
# batched-over-users twins: the K-user cohort as ONE GEMM per layer
# ---------------------------------------------------------------------------
#
# The PR-4 step ran per user and relied on ``jax.vmap`` to batch the K axis.
# These twins take the stacked ``(K, ...)`` weights directly: the patch /
# pool / mask stages run on the merged ``K·B`` leading axis (one elementwise
# program for the whole cohort) and every matmul is a single batched
# ``dot_general`` whose M dimension is the per-user ``B·P`` block — the
# "blocked" layout the Pallas kernels (``kernel.py``) tile over their grid.

_BDN = (((2,), (1,)), ((0,), (0,)))       # (K,M,P) x (K,P,N) -> (K,M,N)


def _bdot(a, b):
    """Batched-over-users matmul in the compute dtype.

    f32 inputs keep the f32-accumulation contract of ``_dot``.  bf16 inputs
    run the backend's *native* bf16 GEMM (no forced-f32 output): on
    AMX/AVX512-BF16 CPUs and TPU MXUs the accumulator is f32 *inside* the
    GEMM microkernel and only the stored result rounds to bf16 — forcing an
    f32 output element type pushes CPU XLA off the native path entirely
    (measured ~2x slower than f32 on the CPU container, under the legacy
    XLA:CPU runtime of the time)."""
    if a.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, _BDN)
    return jax.lax.dot_general(
        a, b, _BDN, preferred_element_type=jnp.float32).astype(a.dtype)


def _bdot32(a, b):
    """Batched grad matmul: f32 result whatever the compute dtype (the
    master-param SGD update never sees a bf16 gradient leaf)."""
    if a.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, _BDN).astype(jnp.float32)
    return jax.lax.dot_general(a, b, _BDN,
                               preferred_element_type=jnp.float32)


def _bT(t: jnp.ndarray) -> jnp.ndarray:
    """Transpose the per-user matrix of a (K, M, N) stack."""
    return jnp.swapaxes(t, 1, 2)


def conv_pool_fwd_k(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, Tuple]:
    """Batched twin of ``conv_pool_fwd``: x (K,B,H,W,C); w (K,3,3,C,O);
    b (K,O) -> a (K,B,H/2,W/2,O) + residuals (pat, eq, relu_m) with a
    leading K.  Values are bit-equal to ``vmap(conv_pool_fwd)`` at f32 —
    the per-user GEMM is the same contraction, just stacked on the batch
    dimension of one ``dot_general``."""
    k, bs, h, wd, c = x.shape
    o = w.shape[-1]
    pat = patches3x3(x.reshape(k * bs, h, wd, c)).reshape(
        k, bs * h * wd, 9 * c)
    z = _bdot(pat, w.reshape(k, 9 * c, o)).reshape(k, bs, h, wd, o)
    zw = z.reshape(k, bs, h // 2, 2, wd // 2, 2, o)
    pz = zw.max(axis=(3, 5))
    bb = b.reshape(k, 1, 1, 1, o).astype(x.dtype)
    a = jnp.maximum(pz + bb, 0.0)
    eqw = (zw == pz[:, :, :, None, :, None, :])
    cnt = eqw.sum(axis=(3, 5), keepdims=True)
    eq = jnp.where(eqw, 1.0 / cnt, 0.0).astype(x.dtype).reshape(
        k, bs, h, wd, o)
    relu_m = (pz + bb > 0).astype(x.dtype)
    return a, (pat, eq, relu_m)


def conv_pool_bwd_k(res: Tuple, w: jnp.ndarray, da: jnp.ndarray,
                    need_dx: bool) -> Tuple:
    """Batched twin of ``conv_pool_bwd``: da (K,B,H/2,W/2,O) ->
    (dw (K,3,3,C,O) f32, db (K,O) f32, dx (K,B,H,W,C) or None)."""
    pat, eq, relu_m = res
    k, bs, h, wd, o = eq.shape
    c = pat.shape[-1] // 9
    dp = da * relu_m                               # (K,B,H/2,W/2,O)
    db = dp.astype(jnp.float32).sum(axis=(1, 2, 3))
    dz = (eq.reshape(k, bs, h // 2, 2, wd // 2, 2, o)
          * dp[:, :, :, None, :, None, :]).reshape(k, bs * h * wd, o)
    dw = _bdot32(_bT(pat), dz).reshape(k, 3, 3, c, o)
    dx = None
    if need_dx:
        dpat = _bdot(dz, _bT(w.reshape(k, 9 * c, o)))
        dx = fold3x3(dpat.reshape(k * bs, h, wd, 9 * c)).reshape(
            k, bs, h, wd, c)
    return dw, db, dx


def fc_chain_fwd_k(flat: jnp.ndarray, params: dict
                   ) -> Tuple[jnp.ndarray, Tuple]:
    """Batched twin of ``fc_chain_fwd``: flat (K,B,F), params leaves
    stacked (K, ...) -> logits (K,B,classes) + (h1, h2)."""
    b1 = params["fc1"]["b"][:, None, :]
    b2 = params["fc2"]["b"][:, None, :]
    b3 = params["fc3"]["b"][:, None, :]
    h1 = jnp.maximum(_bdot(flat, params["fc1"]["w"]) + b1, 0.0)
    h2 = jnp.maximum(_bdot(h1, params["fc2"]["w"]) + b2, 0.0)
    logits = _bdot(h2, params["fc3"]["w"]) + b3
    return logits, (h1, h2)


def fc_chain_bwd_k(flat: jnp.ndarray, res: Tuple, params: dict,
                   dlogits: jnp.ndarray) -> Tuple[dict, jnp.ndarray]:
    """Batched twin of ``fc_chain_bwd``: per-user fc grads (f32) + dflat."""
    h1, h2 = res
    g3 = {"w": _bdot32(_bT(h2), dlogits),
          "b": dlogits.astype(jnp.float32).sum(axis=1)}
    dh2 = _bdot(dlogits, _bT(params["fc3"]["w"])) * (h2 > 0)
    g2 = {"w": _bdot32(_bT(h1), dh2),
          "b": dh2.astype(jnp.float32).sum(axis=1)}
    dh1 = _bdot(dh2, _bT(params["fc2"]["w"])) * (h1 > 0)
    g1 = {"w": _bdot32(_bT(flat), dh1),
          "b": dh1.astype(jnp.float32).sum(axis=1)}
    dflat = _bdot(dh1, _bT(params["fc1"]["w"]))
    return {"fc1": g1, "fc2": g2, "fc3": g3}, dflat


def forward_fwd_ref_k(params: dict, images: jnp.ndarray):
    """Stacked-cohort forward + residuals: params leaves (K, ...),
    images (K,B,H,W,C)."""
    a1, r1 = conv_pool_fwd_k(images, params["conv1"]["w"],
                             params["conv1"]["b"])
    a2, r2 = conv_pool_fwd_k(a1, params["conv2"]["w"], params["conv2"]["b"])
    flat = a2.reshape(a2.shape[0], a2.shape[1], -1)
    logits, rfc = fc_chain_fwd_k(flat, params)
    return logits, (r1, r2, flat, rfc)


def backward_ref_k(params: dict, residuals, dlogits: jnp.ndarray,
                   need_dx: bool = False):
    """Stacked-cohort hand-written VJP: dlogits (K,B,classes) -> per-user
    grads (conv grads f32 via ``_bdot32``)."""
    r1, r2, flat, rfc = residuals
    gfc, dflat = fc_chain_bwd_k(flat, rfc, params, dlogits)
    k, bs, h2_, w2_, o2 = r2[1].shape
    da2 = dflat.reshape(k, bs, h2_ // 2, w2_ // 2, o2)
    dw2, db2, da1 = conv_pool_bwd_k(r2, params["conv2"]["w"], da2, True)
    dw1, db1, dx = conv_pool_bwd_k(r1, params["conv1"]["w"], da1, need_dx)
    grads = {"conv1": {"w": dw1, "b": db1}, "conv2": {"w": dw2, "b": db2},
             **gfc}
    return grads, dx


def forward_ref(params: dict, images: jnp.ndarray) -> jnp.ndarray:
    """Full-model forward, bit-identical to ``cnn.forward_im2col`` at f32
    (pool-first reassociation only — see ``conv_pool_fwd``)."""
    a1, _ = conv_pool_fwd(images, params["conv1"]["w"], params["conv1"]["b"])
    a2, _ = conv_pool_fwd(a1, params["conv2"]["w"], params["conv2"]["b"])
    logits, _ = fc_chain_fwd(a2.reshape(a2.shape[0], -1), params)
    return logits


def forward_fwd_ref(params: dict, images: jnp.ndarray):
    """Forward + all residuals (the ``custom_vjp`` fwd rule)."""
    a1, r1 = conv_pool_fwd(images, params["conv1"]["w"], params["conv1"]["b"])
    a2, r2 = conv_pool_fwd(a1, params["conv2"]["w"], params["conv2"]["b"])
    flat = a2.reshape(a2.shape[0], -1)
    logits, rfc = fc_chain_fwd(flat, params)
    return logits, (r1, r2, flat, rfc)


def backward_ref(params: dict, residuals, dlogits: jnp.ndarray,
                 need_dx: bool = True):
    """Hand-written VJP: dlogits -> dparams (+ dimages when ``need_dx``).

    The training step (``ops.make_loss_grad``) passes ``need_dx=False`` —
    images carry no gradient there; the ``custom_vjp`` wrapper keeps the
    image cotangent for correctness (XLA DCEs it on this jnp path when
    unused, but the Pallas twin cannot rely on DCE inside a kernel)."""
    r1, r2, flat, rfc = residuals
    gfc, dflat = fc_chain_bwd(flat, rfc, params, dlogits)
    b2, h2, w2, o2 = r2[1].shape
    da2 = dflat.reshape(b2, h2 // 2, w2 // 2, o2)
    dw2, db2, da1 = conv_pool_bwd(r2, params["conv2"]["w"], da2, True)
    dw1, db1, dx = conv_pool_bwd(r1, params["conv1"]["w"], da1, need_dx)
    grads = {"conv1": {"w": dw1, "b": db1}, "conv2": {"w": dw2, "b": db2},
             **gfc}
    return grads, dx
