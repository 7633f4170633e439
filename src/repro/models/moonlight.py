"""The DeepSeek-V3 decoder of Moonlight-16B-A3B, as one chip's share.

Pre-norm blocks h = x + MLA(norm(x)); out = h + FFN(norm(h)); the first
``first_k_dense`` blocks keep the dense SwiGLU, the rest the routed MoE
(``moe.routed_moe``: sigmoid router over all experts, the held experts'
part with no token dropped, shared experts).  Embedding, final RMSNorm
and an untied head over the (sliced) vocabulary.

Master weights are float32; products run in ``cfg.dtype`` (bfloat16 on
the round path) with float32 accumulation, the residual stream and the
loss in float32.  Each block is rematerialised in the backward pass and
the MoE blocks run as one ``lax.scan`` over stacked weights.

The correction bias of each MoE layer is a buffer, not a weight: it is
held outside the parameter tree (no gradient, no FL delta, no codec) and
drawn by ``correction_bias`` from a seed.  ``load`` counts, per MoE
layer, the (token, slot) pairs each held expert took."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, mla, moe
from repro.models import module as m

BIAS_SCALE = 1e-2


def n_moe_layers(cfg: ModelConfig) -> int:
    return cfg.num_layers - cfg.first_k_dense


def init(key, cfg: ModelConfig):
    pdt = m.dtype_of(cfg.param_dtype)
    ke, kd, km, kh = jax.random.split(key, 4)

    def block(k, ffn):
        ka, kf = jax.random.split(k)
        return {"attn_norm": layers.init_rmsnorm(cfg.d_model, pdt),
                "attn": mla.init_mla(ka, cfg),
                "mlp_norm": layers.init_rmsnorm(cfg.d_model, pdt),
                "ffn": ffn(kf)}

    params = {
        "embed": layers.init_embedding(ke, cfg),
        "moe": m.stack_layers(
            lambda k: block(k, lambda kf: moe.init_routed_moe(kf, cfg)),
            km, n_moe_layers(cfg)),
        "norm": layers.init_rmsnorm(cfg.d_model, pdt),
        "head": {"w": m.dense_init(kh, cfg.d_model, cfg.vocab_padded, pdt)},
    }
    if cfg.first_k_dense:
        params["dense"] = m.stack_layers(
            lambda k: block(k, lambda kf: layers.init_mlp(kf, cfg)),
            kd, cfg.first_k_dense)
    return params


def correction_bias(seed: int, cfg: ModelConfig) -> jnp.ndarray:
    """(MoE layers, num_experts) float32: N(0, BIAS_SCALE^2) from ``seed``,
    in place of the trained checkpoint's ``e_score_correction_bias``."""
    return BIAS_SCALE * jax.random.normal(
        jax.random.PRNGKey(seed), (n_moe_layers(cfg), cfg.num_experts),
        jnp.float32)


def _attend(p, cfg, x):
    return x + mla.mla(p["attn"], cfg,
                       layers.rmsnorm(p["attn_norm"], x, cfg.norm_eps))


def _dense_block(cfg, x, p):
    h = _attend(p, cfg, x)
    return h + moe._swiglu(p["ffn"], layers.rmsnorm(p["mlp_norm"], h,
                                                    cfg.norm_eps),
                           m.dtype_of(cfg.dtype))


def _moe_block(cfg, x, p, bias):
    h = _attend(p, cfg, x)
    y, held = moe.routed_moe(
        p["ffn"], bias, cfg, layers.rmsnorm(p["mlp_norm"], h, cfg.norm_eps))
    return h + y, held


def hidden(params, bias, cfg: ModelConfig, tokens: jnp.ndarray):
    """Final hidden states (B, S, d) float32 and the MoE ``load``."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    if cfg.first_k_dense:
        dense = jax.checkpoint(partial(_dense_block, cfg))
        x, _ = jax.lax.scan(lambda h, p: (dense(h, p), None), x,
                            params["dense"])
    block = jax.checkpoint(partial(_moe_block, cfg))
    x, held = jax.lax.scan(
        lambda h, pb: block(h, *pb), x, (params["moe"], bias))
    x = layers.rmsnorm(params["norm"], x, cfg.norm_eps)
    return x, {"held": held}


def logits(params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    return mla.mm(h, params["head"]["w"], m.dtype_of(cfg.dtype))


def loss(params, bias, cfg: ModelConfig, tokens, targets):
    """Mean next-token cross-entropy over the batch, and the ``load``."""
    h, load = hidden(params, bias, cfg, tokens)
    z = logits(params, cfg, h)
    nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), load


def zero_load(cfg: ModelConfig):
    return {"held": jnp.zeros((n_moe_layers(cfg), cfg.experts_held),
                              jnp.int32)}


def make_loss_grad(cfg: ModelConfig, bias):
    """``(params, tokens, targets) -> (loss, grads, load)`` for one client's
    batch; grads are float32, the master weights' dtype."""
    def loss_grad(params, tokens, targets):
        (val, load), g = jax.value_and_grad(
            lambda p: loss(p, bias, cfg, tokens, targets),
            has_aux=True)(params)
        return val, g, load
    return loss_grad


def make_evaluate(cfg: ModelConfig, bias, batch: int):
    """``(params, tokens (N, S), targets) -> (loss, next-token accuracy)``
    over the N held-out sequences, ``batch`` at a time."""
    def one(p, xy):
        x, y = xy
        z = logits(p, cfg, hidden(p, bias, cfg, x)[0])
        nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, y[..., None], axis=-1)[..., 0]
        return jnp.sum(nll), jnp.sum(jnp.argmax(z, -1) == y)

    def evaluate(params, tokens, targets):
        n, s = tokens.shape
        xs = (tokens.reshape(n // batch, batch, s),
              targets.reshape(n // batch, batch, s))
        nll, hit = jax.lax.map(partial(one, params), xs)
        return jnp.sum(nll) / (n * s), jnp.sum(hit) / (n * s)
    return evaluate
