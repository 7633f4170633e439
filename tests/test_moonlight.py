"""Moonlight-16B-A3B's layers (DeepSeek-V3: MLA, sigmoid-routed experts of
which this chip holds a share, shared experts) against the plain float32
reference ``models/moonlight_ref.py`` at a tiny size of the same
architecture: a dense layer and 2 MoE layers, d = 64, 8 routed experts of
which 4 are held, vocabulary 256, on seeded random weights."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moonlight_tiny import params as tiny_params
from moonlight_tiny import ref_config, tiny
from repro.models import mla, moe, moonlight
from repro.models import moonlight_ref as ref

@functools.lru_cache(maxsize=None)
def _setup(cfg, seed=0, B=2, S=16):
    p = tiny_params(cfg, seed)
    bias = moonlight.correction_bias(seed + 1, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S + 1), 0,
                              cfg.vocab_size)
    return p, bias, toks[:, :-1], toks[:, 1:]


def _layer(params, grp, j):
    return jax.tree_util.tree_map(lambda a: a[j], params[grp])


def test_mla_forward_matches_reference():
    cfg = tiny()
    p = _layer(_setup(cfg)[0], "moe", 0)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x: mla.mla(p, cfg, x))(x)
        want = jax.jit(lambda x: ref.attention(p, ref_config(cfg), x))(x)
        # causal: the first position attends to itself alone
        first = jax.jit(lambda x: mla.mla(p, cfg, x))(x.at[:, 1:].set(0.0))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(first[:, 0], got[:, 0], rtol=1e-5, atol=1e-6)


def test_moe_layer_no_drops_matches_reference():
    cfg = tiny()
    params, bias = _setup(cfg)[:2]
    p = _layer(params, "moe", 1)["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.d_model))
    prog = jax.jit(lambda p, b: moe.routed_moe(p, b, cfg, x))
    want_fn = jax.jit(lambda p, b: ref.moe(p, b, ref_config(cfg), x))
    with jax.default_matmul_precision("highest"):
        y, held = prog(p, bias[1])
        want, want_held = want_fn(p, bias[1])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(held, want_held)
    # skewed routing: every token to the same experts, none dropped
    p2 = dict(p, router=jnp.zeros_like(p["router"]))
    skew = jnp.zeros((8,)).at[jnp.arange(2, 5)].set(1.0)
    with jax.default_matmul_precision("highest"):
        y2, held2 = prog(p2, skew)
        want2, _ = want_fn(p2, skew)
    np.testing.assert_array_equal(held2, [128, 128, 128, 0])
    np.testing.assert_allclose(y2, want2, rtol=1e-5, atol=1e-5)


def test_router_selects_by_biased_score_and_weights_by_score():
    cfg = tiny(experts_held=8, expert_offset=0)
    x = jnp.ones((1, cfg.d_model))
    router = jnp.zeros((cfg.d_model, 8)).at[:, 0].set(0.002)
    bias = jnp.array([0.0, 0, 0, 0, 0, 0.3, 0.2, 0.1])
    w, idx = moe.route_topk(router, bias, cfg, x)
    # expert 0 has the highest score, 5-7 the highest score + bias
    assert sorted(np.asarray(idx[0]).tolist()) == [5, 6, 7]
    # equal scores (sigmoid 0) -> equal weights, scaled to the factor
    np.testing.assert_allclose(w, np.full((1, 3), cfg.routed_scaling_factor
                                          / 3), rtol=1e-6)


def test_model_loss_and_grads_match_reference():
    cfg = tiny()
    params, bias, x, y = _setup(cfg)
    with jax.default_matmul_precision("highest"):
        l, g, load = jax.jit(moonlight.make_loss_grad(cfg, bias))(
            params, x, y)
    rl, rg = jax.jit(lambda p: ref.loss_and_grad(p, bias, ref_config(cfg),
                                                 x, y))(params)
    np.testing.assert_allclose(l, rl, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(g)
    want = dict(jax.tree_util.tree_leaves_with_path(rg))
    for path, leaf in flat:
        np.testing.assert_allclose(leaf, want[path], rtol=2e-4, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
    _, held = jax.jit(lambda p: ref.logits(p, bias, ref_config(cfg), x))(
        params)
    np.testing.assert_array_equal(load["held"], held)
    # the correction bias is no weight: nothing of it is in the tree
    assert all("bias" not in jax.tree_util.keystr(p) for p, _ in flat)


def test_bf16_compute_stays_near_reference():
    cfg = tiny(dtype="bfloat16")
    params, bias, x, y = _setup(cfg)
    l, _ = jax.jit(lambda p: moonlight.loss(p, bias, cfg, x, y))(params)
    rl = jax.jit(lambda p: ref.loss(p, bias, ref_config(cfg), x, y))(params)
    assert abs(float(l) - float(rl)) < 2e-2 * float(rl)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each chip of an expert-parallel deployment computes its own experts'
    part; the parts of all shares, with the shared expert (which every
    chip computes alike) counted once, give the uncut layer."""
    full = tiny(experts_held=8, expert_offset=0)
    params = _setup(full)[0]
    p = _layer(params, "moe", 0)["ffn"]
    bias = moonlight.correction_bias(3, full)[0]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, full.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = jax.jit(lambda p: moe.routed_moe(p, bias, full, x))(p)
        shared = jax.jit(lambda p: moe._swiglu(p, x, jnp.float32))(
            p["shared"])
        parts, rows = 0.0, 0
        for off in (0, 4):
            share = full.replace(experts_held=4, expert_offset=off)
            ps = dict(p, experts=jax.tree_util.tree_map(
                lambda a: a[off:off + 4], p["experts"]))
            y, held = jax.jit(
                lambda p: moe.routed_moe(p, bias, share, x))(ps)
            parts = parts + (y - shared)
            rows += int(held.sum())
        uncut = jax.jit(lambda p: ref.moe(p, bias, ref_config(full), x))(
            p)[0]
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(parts + shared, uncut, rtol=1e-5, atol=1e-5)
    assert rows == 2 * 32 * full.experts_per_token


@pytest.mark.parametrize("dense", [0, 1])
def test_first_k_dense_layers(dense):
    cfg = tiny(first_k_dense=dense, num_layers=2 + dense)
    params, bias, x, y = _setup(cfg)
    assert ("dense" in params) == bool(dense)
    with jax.default_matmul_precision("highest"):
        l = jax.jit(lambda p: moonlight.loss(p, bias, cfg, x, y)[0])(params)
    rl = jax.jit(lambda p: ref.loss(p, bias, ref_config(cfg), x, y))(params)
    np.testing.assert_allclose(l, rl, rtol=1e-5)


def _unwritten_ragged_dot(ragged_dot):
    """``ragged_dot`` as a TPU's grouped-product kernel may leave its
    outputs: NaN in the rows after the last group, in the input gradient's
    rows there, and in an empty group's weight gradient."""
    @jax.custom_vjp
    def rd(lhs, rhs, gs):
        return rd_fwd(lhs, rhs, gs)[0]

    def dead_rows(a, gs):
        live = jnp.arange(a.shape[0]) < jnp.sum(gs)
        return jnp.where(live[:, None], a, jnp.nan)

    def rd_fwd(lhs, rhs, gs):
        out = ragged_dot(lhs, rhs, gs, preferred_element_type=jnp.float32)
        return dead_rows(out, gs), (lhs, rhs, gs)

    def rd_bwd(res, g):
        lhs, rhs, gs = res
        _, vjp = jax.vjp(lambda a, b: ragged_dot(
            a, b, gs, preferred_element_type=jnp.float32), lhs, rhs)
        dl, dr = vjp(g)
        dr = jnp.where((gs > 0)[:, None, None], dr, jnp.nan)
        return dead_rows(dl, gs).astype(lhs.dtype), dr.astype(rhs.dtype), \
            None

    rd.defvjp(rd_fwd, rd_bwd)
    return lambda lhs, rhs, gs, preferred_element_type=None: rd(lhs, rhs, gs)


def test_held_experts_survive_unwritten_grouped_outputs(monkeypatch):
    """Skewed routing leaves held experts empty; whatever the grouped
    product leaves in the rows and groups it does not write, the layer's
    output and gradients are those of a product that writes zeros."""
    cfg = tiny()
    p = _layer(_setup(cfg)[0], "moe", 0)["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(9), (64, cfg.d_model))
    # every token to experts 0, 3 and 1, 8 of them also to 5: of the held
    # experts 2-5, 2 and 4 take nothing, and most pairs go elsewhere
    w = jnp.full((64, 3), 0.5)
    idx = jnp.tile(jnp.array([0, 3, 1]), (64, 1)).at[:8, 2].set(5)

    def f(ex, x):
        y, held = moe.held_experts(ex, cfg, x, w, idx)
        return jnp.sum(y ** 2), held

    clean = jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))(
        p["experts"], x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_ragged_dot(jax.lax.ragged_dot))
    dirty = jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))(
        p["experts"], x)
    np.testing.assert_array_equal(clean[1], [0, 64, 0, 8])
    np.testing.assert_array_equal(dirty[1], clean[1])
    for a, b in zip(jax.tree_util.tree_leaves(clean[0]),
                    jax.tree_util.tree_leaves(dirty[0])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
