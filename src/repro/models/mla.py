"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 Sec. 2.1.1),
without the query low-rank (``q_lora_rank`` null, as in Moonlight).

Per token x (d):
  q = W_q x, per head [q_nope (head_dim) | q_pe (qk_rope_head_dim)];
  [c (kv_lora_rank) | k_pe (qk_rope_head_dim)] = W_kva x, c RMS-normed;
  W_kvb c, per head [k_nope (head_dim) | v (v_head_dim)];
  q_pe and the one k_pe all heads share are rotated by RoPE;
  causal softmax of (q_nope . k_nope + q_pe . k_pe) / sqrt(head_dim +
  qk_rope_head_dim) over v, and W_o maps the heads' values back to d.

Query and key are 192 wide and values 128 at Moonlight's sizes, which
``attention._sdpa`` (one head width for q, k and v) cannot express.
RoPE rotates the two halves of the rope dims (``rope.apply_rope``); the
published checkpoints store the rope dims interleaved, which is the same
map up to a fixed permutation of W_q's and W_kva's rope columns.

Products take the configuration's compute dtype (``cfg.dtype``) with
float32 accumulation; scores, softmax and the RMSNorm run in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models import module as m
from repro.models.rope import apply_rope, rope_angles

# the latent's RMSNorm keeps the modeling code's default epsilon
KV_NORM_EPS = 1e-6


def mm(x, w, dtype):
    """``x @ w`` with both operands in ``dtype``, accumulated in float32."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def init_mla(key, cfg: ModelConfig):
    pdt = m.dtype_of(cfg.param_dtype)
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": m.dense_init(kq, d, H * (dn + dr), pdt),
        "wkv_a": m.dense_init(ka, d, r + dr, pdt),
        "kv_norm": layers.init_rmsnorm(r, pdt),
        "wkv_b": m.dense_init(kb, r, H * (dn + dv), pdt),
        "wo": m.dense_init(ko, H * dv, d, pdt),
    }


def mla(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Causal MLA over full sequences: x (B, S, d) -> (B, S, d) float32."""
    cd = m.dtype_of(cfg.dtype)
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    angles = rope_angles(jnp.arange(S)[None], dr, cfg.rope_theta)[0]
    q = mm(x, params["wq"], cd).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], apply_rope(q[..., dn:], angles)
    kv = mm(x, params["wkv_a"], cd)
    c = layers.rmsnorm(params["kv_norm"], kv[..., :r], eps=KV_NORM_EPS)
    k_pe = apply_rope(kv[..., None, r:], angles)[:, :, 0]       # (B, S, dr)
    kvb = mm(c, params["wkv_b"], cd).reshape(B, S, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(cd),
                         k_nope.astype(cd),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe.astype(cd),
                           k_pe.astype(cd),
                           preferred_element_type=jnp.float32))
    scores = scores * (dn + dr) ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cd), v.astype(cd),
                     preferred_element_type=jnp.float32)
    return mm(out.reshape(B, S, H * dv), params["wo"], cd)
