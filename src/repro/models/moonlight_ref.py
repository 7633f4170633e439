"""Plain float32 reference of Moonlight-16B-A3B's decoder (DeepSeek-V3,
arXiv:2412.19437 Sec. 2.1; sizes of the model's config.json), for one
chip's share of it: forward, loss and gradients in straightforward
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, with no
kernels, rematerialisation or grouped products: every held expert is
applied to every token and weighted by its (mostly zero) combine weight,
and the MoE layers run one after another in a ``lax.scan``.

``c`` is a dict of the config.json keys (``hidden_size``,
``num_attention_heads``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``kv_lora_rank``, ``rope_theta``, ``rms_norm_eps``,
``n_routed_experts``, ``num_experts_per_tok``, ``routed_scaling_factor``,
``first_k_dense_replace``) plus the cut: ``layers`` (dense ones included)
and ``experts_held`` experts from ``expert_offset``.  Weights are the
program's tree (``models/moonlight.py``): stacked ``dense`` and ``moe``
blocks, ``embed``, ``norm``, ``head``.

Departures from the published model:
- the cut: fewer layers, ``experts_held`` of the 64 routed experts (the
  absent experts' terms are left out, as on one chip of an 8-way expert-
  parallel deployment) and the first rows of the vocabulary;
- random weights, and a seeded random ``e_score_correction_bias`` in
  place of the trained one (experts are chosen by score + bias and
  weighted by the score alone);
- RoPE rotates the two halves of the rope dims, where the checkpoints
  interleave them: the same map up to a permutation of weight columns.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """Rotate the last axis of x (..., S, [H,] D) by positions pos (S,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv              # (S, half)
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def attention(p, c, x):
    """Causal multi-head latent attention, x (B, S, d)."""
    B, S, _ = x.shape
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    pos = jnp.arange(S)
    q = (x @ p["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, c["rope_theta"])
    kv = x @ p["wkv_a"]
    latent = _rms(kv[..., :r], p["kv_norm"]["scale"], 1e-6)
    k_pe = _rope(kv[..., r:], pos, c["rope_theta"])             # (B, S, dr)
    kvb = (latent @ p["wkv_b"]).reshape(B, S, H, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe[:, :, None, :],
                                         (B, S, H, dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_pe], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k) / jnp.sqrt(dn + dr)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                   kvb[..., dn:])
    return o.reshape(B, S, H * dv) @ p["wo"]


def route(router, bias, c, x2d):
    """(T, n_routed_experts) combine weights: top-k of sigmoid score +
    bias, the chosen scores normalised and scaled, zero elsewhere."""
    score = jax.nn.sigmoid(x2d @ router)
    _, idx = jax.lax.top_k(score + jax.lax.stop_gradient(bias),
                           c["num_experts_per_tok"])
    chosen = jnp.take_along_axis(score, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]
    comb = jnp.zeros_like(score)
    return jax.vmap(lambda row, e, v: row.at[e].set(v))(comb, idx, w), idx


def moe(p, bias, c, x):
    """Shared experts plus the held routed experts, each applied to every
    token and weighted by its combine weight (0 where not chosen).
    Returns (y, tokens per held expert)."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    comb, idx = route(p["router"], bias, c, x2d)
    held = c["expert_offset"] + jnp.arange(c["experts_held"])
    ex = p["experts"]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x2d, ex["w_gate"])) \
        * jnp.einsum("td,edf->etf", x2d, ex["w_up"])
    out = jnp.einsum("etf,efd->etd", h, ex["w_down"])
    w = comb[:, c["expert_offset"]:c["expert_offset"] + c["experts_held"]]
    y = _swiglu(p["shared"], x2d) + jnp.einsum("te,etd->td", w, out)
    return y.reshape(B, S, d), jnp.sum(idx[..., None] == held, axis=(0, 1))


def _block(p, c, x, ffn):
    eps = c["rms_norm_eps"]
    x = x + attention(p["attn"], c, _rms(x, p["attn_norm"]["scale"], eps))
    return ffn(_rms(x, p["mlp_norm"]["scale"], eps), x)


def logits(params, bias, c, tokens):
    """(logits (B, S, V), tokens per held expert (MoE layers, held)).
    The leading dense layers, then the MoE layers one after another."""
    x = params["embed"]["table"][tokens]
    for j in range(c["first_k_dense_replace"]):
        p = jax.tree_util.tree_map(lambda a: a[j], params["dense"])
        x = _block(p, c, x, lambda h, x: x + _swiglu(p["ffn"], h))

    def moe_layer(x, pb):
        p, b = pb
        out = {}

        def ffn(h, x):
            y, out["held"] = moe(p["ffn"], b, c, h)
            return x + y

        x = _block(p, c, x, ffn)
        return x, out["held"]

    x, held = jax.lax.scan(moe_layer, x, (params["moe"], bias))
    x = _rms(x, params["norm"]["scale"], c["rms_norm_eps"])
    return x @ params["head"]["w"], held


def loss(params, bias, c, tokens, targets):
    with jax.default_matmul_precision("highest"):
        z = logits(params, bias, c, tokens)[0]
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, targets[..., None], -1)[..., 0]
        return jnp.mean(nll)


def loss_and_grad(params, bias, c, tokens, targets):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, bias, c, tokens, targets)
