"""Mixture-of-Experts FFN: top-k router + capacity-based scatter dispatch.

Two dispatch strategies, both static-shape and GSPMD-partitionable:

- ``scatter`` (default): tokens are scattered into a per-expert capacity
  buffer (E, C, d) via computed slot indices, experts run as a vmapped SwiGLU
  over the expert axis, outputs gather back.  FLOPs ~= useful FLOPs; the
  buffer is the all-to-all payload when experts are expert-parallel.
- ``dense``: every expert processes every token and the router combine is an
  einsum.  FLOPs inflate by E/k but there is no dispatch traffic — profitable
  for fine-grained small experts (granite) at small token counts; kept as a
  first-class option for the §Perf comparison.

Router aux loss is the Switch load-balance term  E * sum_e f_e * P_e.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models import module as m

CAPACITY_FACTOR = 1.25


def init_moe(key, cfg: ModelConfig):
    pdt = m.dtype_of(cfg.param_dtype)
    kr, kg, ku, kd = jax.random.split(key, 4)
    E, d, ff = cfg.num_experts, cfg.d_model, (cfg.moe_d_ff or cfg.d_ff)

    def one_expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "w_gate": m.dense_init(k1, d, ff, pdt),
            "w_up": m.dense_init(k2, d, ff, pdt),
            "w_down": m.dense_init(k3, ff, d, pdt),
        }

    return {
        "router": m.dense_init(kr, d, E, pdt, scale=0.02),
        "experts": m.stack_layers(one_expert, jax.random.fold_in(kg, 7), E),
    }


def _expert_ffn(wp, x):
    """x: (..., d) with stacked expert weights already selected/vmapped."""
    dt = x.dtype
    gate = x @ wp["w_gate"].astype(dt)
    up = x @ wp["w_up"].astype(dt)
    return (jax.nn.silu(gate) * up) @ wp["w_down"].astype(dt)


def _route(params, cfg: ModelConfig, x2d: jnp.ndarray):
    """Router top-k.  x2d: (T, d) -> (weights (T,k), experts (T,k), aux)."""
    logits = (x2d @ params["router"].astype(x2d.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    k = cfg.experts_per_token
    top_w, top_e = jax.lax.top_k(probs, k)                    # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # Switch load-balance aux: fraction routed vs mean prob, per expert
    T = x2d.shape[0]
    onehot = jax.nn.one_hot(top_e[:, 0], cfg.num_experts)     # primary route
    f = jnp.mean(onehot, axis=0)
    P = jnp.mean(probs, axis=0)
    aux = cfg.num_experts * jnp.sum(f * P)
    return top_w.astype(x2d.dtype), top_e, aux


def moe_dense(params, cfg: ModelConfig,
              x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-experts einsum path.  x: (B, S, d) -> (y, aux).

    The router combine is folded INTO the down-projection contraction
    (§Perf granite iteration 3): contracting e and f in one einsum makes the
    tensor-parallel partial-sum all-reduce carry (T, d) instead of (T, E, d)
    — an E x collective-bytes reduction (40x for granite)."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    top_w, top_e, aux = _route(params, cfg, x2d)
    dt = x.dtype
    ex = params["experts"]
    gate = jnp.einsum("td,edf->tef", x2d, ex["w_gate"].astype(dt))
    up = jnp.einsum("td,edf->tef", x2d, ex["w_up"].astype(dt))
    combine = jnp.zeros((B * S, cfg.num_experts), dt)
    combine = jax.vmap(lambda c, e, w: c.at[e].add(w))(combine, top_e, top_w)
    hidden = (jax.nn.silu(gate) * up) * combine[..., None]    # (T, E, F)
    y = jnp.einsum("tef,efd->td", hidden, ex["w_down"].astype(dt))
    return y.reshape(B, S, d), aux


def moe_scatter(params, cfg: ModelConfig, x: jnp.ndarray,
                act=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity scatter/gather path.  x: (B, S, d) -> (y, aux)."""
    from repro.sharding.apply import constrain

    B, S, d = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.experts_per_token
    C = max(8, int(CAPACITY_FACTOR * T * k / E + 0.5))
    x2d = x.reshape(T, d)
    top_w, top_e, aux = _route(params, cfg, x2d)

    flat_e = top_e.reshape(T * k)                             # (T*k,)
    flat_w = top_w.reshape(T * k)
    # position of each routed token within its expert, via cumsum of one-hots
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)       # (T*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)          # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C                                            # capacity drop
    slot = jnp.where(keep, flat_e * C + pos, E * C)           # E*C = waste slot

    buf = jnp.zeros((E * C + 1, d), x.dtype)
    src = jnp.repeat(x2d, k, axis=0) if k > 1 else x2d
    buf = buf.at[slot].set(src, mode="drop")
    expert_in = buf[: E * C].reshape(E, C, d)
    # expert-parallel over the model axis when E divides it (llama4);
    # otherwise experts are replicated and sharded inside (granite)
    e_ax = "M" if (act is not None and E % act.get("model_size", 16) == 0) else None
    expert_in = constrain(expert_in, act, e_ax, None, None)
    expert_out = jax.vmap(_expert_ffn)(params["experts"], expert_in)
    expert_out = constrain(expert_out, act, e_ax, None, None)

    flat_out = jnp.concatenate(
        [expert_out.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0)
    y_tok = flat_out[slot] * (flat_w * keep.astype(flat_w.dtype))[:, None]
    y = y_tok.reshape(T, k, d).sum(axis=1) if k > 1 else y_tok
    return y.reshape(B, S, d), aux


def moe_ffn(params, cfg: ModelConfig, x: jnp.ndarray,
            dispatch: str = "scatter", act=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if dispatch == "dense":
        return moe_dense(params, cfg, x)
    return moe_scatter(params, cfg, x, act=act)


# ---------------------------------------------------------------------------
# DeepSeek-V3 routed experts (moonlight), this chip's share, no drops
# ---------------------------------------------------------------------------

def init_routed_moe(key, cfg: ModelConfig):
    """Router over all ``num_experts``, the ``experts_held`` experts this
    chip holds (stacked on a leading axis) and the shared experts, one
    SwiGLU of width ``n_shared_experts * moe_d_ff``."""
    pdt = m.dtype_of(cfg.param_dtype)
    kr, ke, ks = jax.random.split(key, 3)
    d, ff = cfg.d_model, cfg.moe_d_ff

    def one_expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w_gate": m.dense_init(k1, d, ff, pdt),
                "w_up": m.dense_init(k2, d, ff, pdt),
                "w_down": m.dense_init(k3, ff, d, pdt)}

    return {
        "router": m.dense_init(kr, d, cfg.num_experts, pdt),
        "experts": m.stack_layers(one_expert, ke, cfg.experts_held),
        "shared": layers.init_mlp(ks, cfg, cfg.n_shared_experts * ff),
    }


def router_scores(logits: jnp.ndarray) -> jnp.ndarray:
    """Expert affinities from router logits: DeepSeek-V3's sigmoid."""
    return jax.nn.sigmoid(logits)


def route_topk(router_w, bias, cfg: ModelConfig, x2d: jnp.ndarray):
    """(weights (T, k), experts (T, k)) over all ``num_experts``.

    Experts are chosen by the top-k of score + ``bias`` (the correction
    bias, a buffer that takes no gradient); the chosen scores alone,
    normalised to sum 1 and scaled by ``routed_scaling_factor``, weight
    them.  The router's product runs at float32 ``highest``, so that a
    low compute precision elsewhere does not reorder the top-k."""
    logits = jnp.dot(x2d.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = router_scores(logits)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                           cfg.experts_per_token)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


@jax.custom_vjp
def _live_experts(experts, counts):
    """``experts``, whose gradient is zeroed for the experts that took no
    row: the grouped product's weight gradient leaves an empty group's
    output unwritten on a TPU."""
    return experts


def _live_experts_fwd(experts, counts):
    return experts, counts


def _live_experts_bwd(counts, g):
    return (jax.tree_util.tree_map(
        lambda a: jnp.where((counts > 0).reshape((-1,) + (1,) * (a.ndim - 1)),
                            a, 0), g), None)


_live_experts.defvjp(_live_experts_fwd, _live_experts_bwd)


def held_experts(experts, cfg: ModelConfig, x2d: jnp.ndarray, weights,
                 idx):
    """This chip's routed experts' part of the layer, with no token dropped.

    ``idx`` (T, k) names experts over all ``num_experts``; the chip holds
    ``[expert_offset, expert_offset + experts_held)``.  The (token, slot)
    pairs are sorted by held expert (pairs routed elsewhere last) and each
    expert's rows go through one grouped product (``lax.ragged_dot``);
    pairs routed to absent experts add nothing here.  Returns ``(y (T, d)
    float32, held (experts_held,) int32)``, ``held`` the rows each expert's
    grouped product took."""
    cd = m.dtype_of(cfg.dtype)
    T, k = idx.shape
    Eh = cfg.experts_held
    local = idx.reshape(T * k) - cfg.expert_offset
    held = (local >= 0) & (local < Eh)
    key = jnp.where(held, local, Eh)
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
    w = jnp.where(held, weights.reshape(T * k), 0.0)
    # the grouped products leave the rows after the last group (pairs
    # routed elsewhere) unwritten on a TPU: zero them on the way in and
    # out, so that neither they nor their gradients reach a token
    live = (jnp.arange(T * k) < jnp.sum(counts))[:, None]
    experts = _live_experts(experts, counts)

    def grouped(a, w):
        return jnp.where(live, jax.lax.ragged_dot(
            a, w.astype(cd), counts, preferred_element_type=jnp.float32),
            0.0)

    xs = jnp.where(live, x2d[order // k], 0.0).astype(cd)
    h = jax.nn.silu(grouped(xs, experts["w_gate"])) \
        * grouped(xs, experts["w_up"])
    rows = grouped(h.astype(cd), experts["w_down"])
    inv = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    y = (rows[inv] * w[:, None]).reshape(T, k, -1).sum(axis=1)
    return y, counts


def _swiglu(p, x, cd):
    gate = jnp.dot(x.astype(cd), p["w_gate"].astype(cd),
                   preferred_element_type=jnp.float32)
    up = jnp.dot(x.astype(cd), p["w_up"].astype(cd),
                 preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(gate) * up).astype(cd),
                   p["w_down"].astype(cd), preferred_element_type=jnp.float32)


def routed_moe(params, bias, cfg: ModelConfig, x: jnp.ndarray):
    """DeepSeek-V3 MoE FFN, this chip's share: the shared experts for every
    token plus the held routed experts' weighted outputs.  x (B, S, d)
    -> (y float32, held rows per expert)."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    w, idx = route_topk(params["router"], bias, cfg, x2d)
    y, held = held_experts(params["experts"], cfg, x2d, w, idx)
    y = y + _swiglu(params["shared"], x2d, m.dtype_of(cfg.dtype))
    return y.reshape(B, S, d), held
