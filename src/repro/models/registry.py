"""Model registry: resolve an arch id to a uniform model API."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax

from repro.configs.base import ModelConfig, get_config
from repro.models import cnn as cnn_mod
from repro.models import module as m
from repro.models import moonlight
from repro.models import transformer as tf


@dataclass(frozen=True)
class Model:
    """Uniform handle: pure init/apply callables bound to one config."""
    cfg: ModelConfig
    init: Callable[[jax.Array], Dict[str, Any]]
    forward: Callable[..., Any]            # (params, inputs, opts) -> (logits, aux)
    decode: Optional[Callable[..., Any]]   # (params, token, state, position, opts)
    init_decode_state: Optional[Callable[..., Any]]

    def param_count(self, params) -> int:
        return m.param_count(params)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "cnn":
        return Model(
            cfg=cfg,
            init=lambda key: cnn_mod.init_cnn(key, cfg.vocab_size, cfg.d_model),
            forward=lambda p, inputs, opts=None: (
                cnn_mod.forward(p, inputs["images"]), 0.0),
            decode=None,
            init_decode_state=None,
        )
    has_decode = not cfg.is_encoder_only
    return Model(
        cfg=cfg,
        init=lambda key: tf.init_model(key, cfg),
        forward=lambda p, inputs, opts=None: tf.forward_full(p, cfg, inputs, opts),
        decode=(lambda p, token, state, position, opts=None:
                tf.decode_step(p, cfg, token, state, position, opts)
                ) if has_decode else None,
        init_decode_state=(lambda batch, context_len, dtype:
                           tf.init_decode_state(cfg, batch, context_len, dtype)
                           ) if has_decode else None,
    )


def get_model(arch_id: str) -> Model:
    return build_model(get_config(arch_id))


def get_reduced_model(arch_id: str) -> Model:
    return build_model(get_config(arch_id).reduced())


@dataclass(frozen=True)
class ClientModel:
    """What the HSFL round needs of a language model each client trains:
    the trainable weights' ``init``, the non-trainable ``buffers`` (no
    gradient, no FL delta, no codec), one client's training step
    ``loss_and_grad(params, tokens, targets) -> (loss, grads, load)``
    with ``zero_load`` the zero of its counters, ``logits(params,
    tokens)`` and ``evaluate(batch)`` -> ``(params, tokens, targets) ->
    (loss, accuracy)`` over held-out sequences ``batch`` at a time."""
    cfg: ModelConfig
    init: Callable[[jax.Array], Dict[str, Any]]
    buffers: Any
    loss_and_grad: Callable[..., Any]
    logits: Callable[..., Any]
    evaluate: Callable[[int], Callable[..., Any]]
    zero_load: Any


def client_model(cfg: ModelConfig, seed: int = 0) -> ClientModel:
    """The round's handle on ``cfg``; ``seed`` draws its buffers.  The
    round trains DeepSeek-V3-style models (latent attention, routed
    experts) only."""
    if not cfg.kv_lora_rank:
        raise NotImplementedError(
            f"{cfg.name}: the HSFL round trains models with latent "
            f"attention and routed experts (DeepSeek-V3 blocks) only")
    bias = moonlight.correction_bias(seed, cfg)
    return ClientModel(
        cfg=cfg, init=lambda key: moonlight.init(key, cfg), buffers=bias,
        loss_and_grad=moonlight.make_loss_grad(cfg, bias),
        logits=lambda p, tokens: moonlight.logits(
            p, cfg, moonlight.hidden(p, bias, cfg, tokens)[0]),
        evaluate=lambda batch: moonlight.make_evaluate(cfg, bias, batch),
        zero_load=moonlight.zero_load(cfg))
