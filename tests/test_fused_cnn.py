"""Fused CNN training-step kernels (kernels/fused_cnn) — the PR-4 suite.

Pins, per the acceptance criteria:
- f32 value equivalence of the fused forward (xla custom-VJP path AND the
  Pallas kernels in interpret mode) against ``cnn.forward_im2col`` at the
  bit level;
- the hand-written VJP against ``jax.grad`` of the reference, including
  the pool tie-splitting semantics on real digits data (constant-zero
  backgrounds produce 4-way pool ties);
- the bf16 mixed-precision policy: f32 master params/grads and a loss
  curve within tolerance of the f32 run;
- donation: the fused round's params (and async straggler stack) buffers
  alias their outputs instead of being copied every round;
- the sweepable delta-codec block width.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hsfl import HSFLConfig, HSFLSimulation, model_compress_ratio
from repro.data.synthetic import make_digits
from repro.kernels.fused_cnn.ops import (ForwardPolicy, make_eval_forward,
                                         make_forward, make_loss_grad,
                                         make_stacked_epoch_fn,
                                         make_stacked_loss_grad)
from repro.models import cnn as cnn_mod
from repro.training.loss import cross_entropy

POLICIES = [ForwardPolicy(),                                  # xla / f32
            ForwardPolicy(kernel="pallas", interpret=True)]   # pallas / f32


@pytest.fixture(scope="module")
def fixture_data():
    params = cnn_mod.init_cnn(jax.random.PRNGKey(3))
    ds = make_digits(64, seed=0)
    # real digits: constant-zero backgrounds exercise the pool-tie and
    # dead-ReLU branches of the hand-written backward
    x = jnp.asarray(ds.x[:32])
    y = jnp.asarray(ds.y[:32])
    return params, x, y


@pytest.mark.parametrize("policy", POLICIES,
                         ids=lambda p: p.kernel)
def test_forward_bit_equivalence_f32(policy, fixture_data):
    params, x, _ = fixture_data
    want = cnn_mod.forward_im2col(params, x)
    got = make_forward(policy)(params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_eval = make_eval_forward(policy)(params, x)
    np.testing.assert_array_equal(np.asarray(got_eval), np.asarray(want))


@pytest.mark.parametrize("policy", POLICIES,
                         ids=lambda p: p.kernel)
def test_custom_vjp_matches_autodiff(policy, fixture_data):
    params, x, y = fixture_data
    gref = jax.grad(
        lambda q: cross_entropy(cnn_mod.forward_im2col(q, x), y))(params)
    fwd = make_forward(policy)
    got = jax.grad(lambda q: cross_entropy(fwd(q, x), y))(params)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(gref),
            jax.tree_util.tree_leaves_with_path(got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-7, rtol=1e-5,
                                   err_msg=jax.tree_util.keystr(pa))


@pytest.mark.parametrize("policy", POLICIES + [ForwardPolicy(kernel="im2col")],
                         ids=lambda p: p.kernel)
def test_fused_loss_grad_matches_autodiff(policy, fixture_data):
    """make_loss_grad (the epoch-scan training step: closed-form softmax-CE
    cotangent + hand-written backward) vs jax.grad of the reference."""
    from repro.kernels.fused_cnn.ops import make_loss_grad
    params, x, y = fixture_data
    lref, gref = jax.value_and_grad(
        lambda q: cross_entropy(cnn_mod.forward_im2col(q, x), y))(params)
    loss, g = make_loss_grad(policy)(params, x, y)
    np.testing.assert_allclose(float(loss), float(lref), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gref),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-7, rtol=1e-5)


def test_pool_first_tie_gradients_match_on_synthetic_ties():
    """Windows with exact positive ties must split the pool gradient by
    1/count, like jax's reduce-max rule — pinned on a crafted input."""
    params = cnn_mod.init_cnn(jax.random.PRNGKey(0))
    x = jnp.ones((2, 28, 28, 1))                 # maximal tie pressure
    y = jnp.asarray([1, 7])
    gref = jax.grad(
        lambda q: cross_entropy(cnn_mod.forward_im2col(q, x), y))(params)
    fwd = make_forward(ForwardPolicy())
    got = jax.grad(lambda q: cross_entropy(fwd(q, x), y))(params)
    for a, b in zip(jax.tree_util.tree_leaves(gref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-7, rtol=1e-5)


def test_policy_validation():
    with pytest.raises(ValueError, match="kernel"):
        make_forward(ForwardPolicy(kernel="cuda"))
    with pytest.raises(ValueError, match="precision"):
        make_forward(ForwardPolicy(precision="fp8"))
    with pytest.raises(ValueError, match="kernel"):
        HSFLSimulation(HSFLConfig(rounds=1, n_uavs=4, k_select=2,
                                  n_train=100, n_test=50, kernel="nope"))


# -- bf16 mixed precision -----------------------------------------------------

def _train(fwd, params, x, y, steps=150, lr=0.1, bs=32):
    def step(p, i):
        bx = jax.lax.dynamic_slice_in_dim(x, (i * bs) % (x.shape[0] - bs),
                                          bs)
        by = jax.lax.dynamic_slice_in_dim(y, (i * bs) % (x.shape[0] - bs),
                                          bs)
        g = jax.grad(lambda q: cross_entropy(fwd(q, bx), by))(p)
        p = jax.tree_util.tree_map(lambda w, gg: w - lr * gg, p, g)
        return p, cross_entropy(fwd(p, bx), by)

    params, losses = jax.lax.scan(step, params, jnp.arange(steps))
    return params, np.asarray(losses)


def test_bf16_policy_loss_curve_tracks_f32():
    """The mixed-precision step must train: master params/grads stay f32,
    and the loss curve stays within tolerance of the f32 run (the
    'paper-comparable accuracy' pin — bf16 is a compute dtype, not a
    different algorithm)."""
    params = cnn_mod.init_cnn(jax.random.PRNGKey(1))
    ds = make_digits(400, seed=2)
    x, y = jnp.asarray(ds.x), jnp.asarray(ds.y)
    f32 = make_forward(ForwardPolicy())
    bf16 = make_forward(ForwardPolicy(precision="bf16"))
    p32, l32 = _train(f32, params, x, y)
    pbf, lbf = _train(bf16, params, x, y)
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(pbf))
    # both learn…
    assert l32[-5:].mean() < 0.2 * l32[0]
    assert lbf[-5:].mean() < 0.2 * lbf[0]
    # …and the bf16 curve tracks f32 within a small absolute band
    assert abs(float(lbf[-5:].mean() - l32[-5:].mean())) < 0.15, (
        lbf[-5:], l32[-5:])


def test_bf16_grads_are_f32_accumulated():
    params = cnn_mod.init_cnn(jax.random.PRNGKey(1))
    x = jnp.asarray(make_digits(16, seed=0).x)
    y = jnp.asarray(make_digits(16, seed=0).y)
    fwd = make_forward(ForwardPolicy(precision="bf16"))
    g = jax.grad(lambda q: cross_entropy(fwd(q, x), y))(params)
    assert all(l.dtype == jnp.float32 for l in jax.tree_util.tree_leaves(g))


# -- donation: no spurious copies of the round carries ------------------------

def test_fused_round_donates_params():
    """The opt round must consume its params buffer and alias it to the
    output (buffer-identity check, CPU donation is real in this jax)."""
    from repro.core.fused_round import build_fused_round
    fn = build_fused_round(scheme="opt", local_epochs=2, steps_per_epoch=1,
                           lr=0.01, tau_max=30.0, probe_epochs=(),
                           forward=ForwardPolicy())
    params = cnn_mod.init_cnn(jax.random.PRNGKey(0))
    ptr0 = params["fc1"]["w"].unsafe_buffer_pointer()
    K, e, steps, bs = 2, 2, 1, 4
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(e, K, steps, bs, 28, 28, 1)),
                     jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, (e, K, steps, bs)))
    chan = {"rates": jnp.full((e, K), 1e6, jnp.float32),
            "outages": jnp.zeros((e, K), bool),
            "payload_bits": jnp.full((K,), 8e6, jnp.float32),
            "tau_extra0": jnp.zeros((K,), jnp.float32),
            "final_rate": jnp.full((K,), 1e6, jnp.float32),
            "final_outage": jnp.zeros((K,), bool),
            "train_time": jnp.full((K,), 1.0, jnp.float32),
            "valid": jnp.ones((K,), bool)}
    new_params, stats = fn(params, xs, ys, chan)
    jax.block_until_ready(new_params)
    assert params["fc1"]["w"].is_deleted(), \
        "params were not donated — the round copies the model every dispatch"
    assert new_params["fc1"]["w"].unsafe_buffer_pointer() == ptr0, \
        "donated params buffer was not aliased to the output"


def test_sweep_group_fn_donates_carry():
    """The sweep program must consume the DeviceSimCarry (params stack,
    fleet, stragglers) rather than copying it at the dispatch boundary."""
    from repro.core.sweep import (SweepSpec, _build_group_fn,
                                  _group_inputs, compile_spec)
    spec = SweepSpec(base=HSFLConfig(rounds=2, n_uavs=6, k_select=2,
                                     n_train=200, n_test=50,
                                     steps_per_epoch=1, local_epochs=2),
                     seeds=(0,), schemes=(("opt", {"b": 2.0}),))
    group = compile_spec(spec)[0]
    fn = _build_group_fn(group)
    carry0, round_keys, data, cfg_stack = _group_inputs(group, 2)
    leaf = carry0.params["fc1"]["w"]
    carry_out, metrics = fn(carry0, round_keys, data, cfg_stack)
    jax.block_until_ready(metrics)
    assert leaf.is_deleted(), "DeviceSimCarry was not donated"
    assert carry_out.params["fc1"]["w"].shape == leaf.shape


# -- the pallas policy end to end through a (tiny) fused round ----------------

def test_pallas_round_matches_xla_round():
    """kernel='pallas' must reproduce the default path through a real
    fused round: identical count trajectories, params within float noise
    (both backwards are the same mask algebra, modulo reassociation)."""
    def run(kernel):
        cfg = HSFLConfig(rounds=2, n_uavs=8, k_select=4, n_train=400,
                         n_test=100, steps_per_epoch=2, local_epochs=3,
                         scheme="opt", b=2, seed=0, kernel=kernel)
        sim = HSFLSimulation(cfg)
        delayed, logs = [], []
        for t in range(1, cfg.rounds + 1):
            log, delayed = sim.run_round(t, delayed)
            logs.append((log.selected, log.arrived_final, log.used_snapshot,
                         log.dropped, round(log.bytes_sent, 3)))
        return logs, sim.params

    logs_x, p_x = run("xla")
    logs_p, p_p = run("pallas")
    assert logs_x == logs_p
    diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree_util.tree_leaves(p_x),
                               jax.tree_util.tree_leaves(p_p)))
    assert diff < 1e-6, diff


# -- sweepable codec block width ----------------------------------------------

def test_codec_block_ratio_frontier():
    """Smaller quantization groups cost more scale overhead per wire byte:
    the overhead-vs-delay frontier of arXiv:2405.00681."""
    from repro.kernels.delta_codec.ops import codec_ratio
    n = 123_456
    r = [codec_ratio(n, b) for b in (128, 256, 512, 1024)]
    assert r == sorted(r, reverse=True)
    assert r[2] == codec_ratio(n)                  # default block is 512
    with pytest.raises(ValueError, match="128"):
        codec_ratio(n, 100)


def test_codec_block_quantize_roundtrip():
    from repro.kernels.delta_codec.kernel import (dequantize_blocks,
                                                  quantize_blocks)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    q, s = quantize_blocks(x, interpret=True)      # block from the shape
    xd = dequantize_blocks(q, s, interpret=True)
    assert q.shape == (256, 256) and s.shape == (256, 1)
    assert float(jnp.max(jnp.abs(xd - x))) <= float(jnp.max(s)) / 2 + 1e-7


def test_codec_block_is_group_static_and_threads_through():
    """codec_block forks a sweep group (program static) and changes the
    derived compress ratio end to end."""
    from repro.core.sweep import SweepSpec, compile_spec, run_sweep
    base = HSFLConfig(rounds=2, n_uavs=6, k_select=2, n_train=200,
                      n_test=50, steps_per_epoch=1, local_epochs=4,
                      use_delta_codec=True)
    r256 = model_compress_ratio(HSFLConfig(use_delta_codec=True,
                                           codec_block=256))
    r512 = model_compress_ratio(HSFLConfig(use_delta_codec=True))
    assert r256 > r512
    spec = SweepSpec(base=base, seeds=(0,),
                     schemes=(("opt", {"b": 2.0}),
                              ("opt", {"b": 2.0, "codec_block": 256})))
    groups = compile_spec(spec)
    assert [g.base.codec_block for g in groups] == [512, 256]
    res = run_sweep(spec, mesh=None)
    assert res.n_programs == 2                     # block width is a static
    for g in res.groups:
        assert np.all(np.isfinite(g.metrics["test_loss"]))


# -- PR 7: blocked stacked-cohort kernels (user axis inside the grid) ---------

STACKED_POLICIES = [ForwardPolicy(),                                # xla
                    ForwardPolicy(kernel="pallas", interpret=True)]


def _stack_fixture(k, bs=8, seed=0):
    """Stacked ``(K, ...)`` params + per-user digit shards (real digits:
    zero backgrounds exercise pool-tie and dead-ReLU mask branches)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), k)
    params = jax.vmap(lambda kk: cnn_mod.init_cnn(kk))(keys)
    ds = make_digits(k * bs, seed=seed + 1)
    x = jnp.asarray(ds.x).reshape(k, bs, 28, 28, 1)
    y = jnp.asarray(ds.y).reshape(k, bs)
    return params, x, y


def _vmapped_autodiff_loss_grad(params, x, y):
    def one(p, bx, by):
        return jax.value_and_grad(
            lambda q: cross_entropy(cnn_mod.forward_im2col(q, bx), by))(p)

    return jax.vmap(one)(params, x, y)


@pytest.mark.parametrize("k", [1, 3, 10])
@pytest.mark.parametrize("policy", STACKED_POLICIES, ids=lambda p: p.kernel)
def test_stacked_forward_bit_equivalence_f32(policy, k):
    """Blocked forward (xla batched dot_general AND the grid-tiled Pallas
    kernels in interpret mode) is bit-equal to vmap(forward_im2col) at f32
    for cohort sizes 1, 3, and the paper's K=10."""
    from repro.kernels.fused_cnn.ops import _impl_stacked
    params, x, _ = _stack_fixture(k)
    want = cnn_mod.forward_im2col_k(params, x)
    fwd_res_k, _ = _impl_stacked(policy)
    got, _ = fwd_res_k(params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k", [1, 3, 10])
@pytest.mark.parametrize("policy", STACKED_POLICIES, ids=lambda p: p.kernel)
def test_stacked_loss_grad_matches_vmapped_autodiff(policy, k):
    params, x, y = _stack_fixture(k)
    lref, gref = _vmapped_autodiff_loss_grad(params, x, y)
    loss, g = make_stacked_loss_grad(policy)(params, x, y)
    assert loss.shape == (k,)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(lref),
                               rtol=1e-5)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(gref),
            jax.tree_util.tree_leaves_with_path(g)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-7, rtol=1e-5,
                                   err_msg=jax.tree_util.keystr(pa))


@pytest.mark.parametrize("policy", STACKED_POLICIES, ids=lambda p: p.kernel)
def test_stacked_pool_tie_and_dead_relu_gradients(policy):
    """Constant-ones images put every 2x2 pool window in a 4-way positive
    tie, and random conv2 signs leave dead-ReLU lanes: the blocked
    backward must split/zero exactly like jax's reduce-max rule."""
    k, bs = 3, 2
    params = jax.vmap(lambda kk: cnn_mod.init_cnn(kk))(
        jax.random.split(jax.random.PRNGKey(0), k))
    x = jnp.ones((k, bs, 28, 28, 1))
    y = jnp.tile(jnp.asarray([1, 7]), (k, 1))
    lref, gref = _vmapped_autodiff_loss_grad(params, x, y)
    loss, g = make_stacked_loss_grad(policy)(params, x, y)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(lref),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gref),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-7, rtol=1e-5)


def test_block_k_tiling_and_padding_match_full_cohort():
    """block_k tiles the grid (divisor) or pads the cohort (non-divisor:
    K=10 @ block_k=4 pads 2 phantom users) without changing the result;
    on the xla path the knob is an accepted no-op."""
    params, x, y = _stack_fixture(10, bs=4)
    want_l, want_g = make_stacked_loss_grad(ForwardPolicy())(params, x, y)
    for policy in (
            ForwardPolicy(kernel="pallas", interpret=True, block_k=5),
            ForwardPolicy(kernel="pallas", interpret=True, block_k=4),
            ForwardPolicy(block_k=5)):
        loss, g = make_stacked_loss_grad(policy)(params, x, y)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(want_l),
                                   rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(want_g),
                        jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=2e-7, rtol=1e-5)
    with pytest.raises(ValueError, match="block_k"):
        make_stacked_loss_grad(ForwardPolicy(block_k=-1))


def test_batch_users_false_is_the_vmapped_step():
    """batch_users=False must be *bit-identical* to vmap(make_loss_grad):
    it IS the PR-4 composition, kept as the blocked path's in-tree twin."""
    params, x, y = _stack_fixture(4)
    loss_v, g_v = make_stacked_loss_grad(
        ForwardPolicy(batch_users=False))(params, x, y)
    loss_m, g_m = jax.vmap(make_loss_grad(ForwardPolicy()))(params, x, y)
    np.testing.assert_array_equal(np.asarray(loss_v), np.asarray(loss_m))
    for a, b in zip(jax.tree_util.tree_leaves(g_m),
                    jax.tree_util.tree_leaves(g_v)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


def _epoch_fixture(k=3, steps=4, bs=10, seed=0):
    params, _, _ = _stack_fixture(k, bs=1, seed=seed)
    ds = make_digits(k * steps * bs, seed=seed + 2)
    xs = jnp.asarray(ds.x).reshape(k, steps, bs, 28, 28, 1)
    ys = jnp.asarray(ds.y).reshape(k, steps, bs)
    return params, xs, ys


def test_stacked_epoch_blocked_matches_vmapped_bitwise():
    """At f32 the blocked epoch (user axis in the kernel grid) and the
    vmapped epoch produce bit-identical parameter trajectories: the
    batched dot_generals keep f32 accumulation and contraction order."""
    params, xs, ys = _epoch_fixture()
    blocked = make_stacked_epoch_fn(ForwardPolicy(), 0.05)
    vmapped = make_stacked_epoch_fn(ForwardPolicy(batch_users=False), 0.05)
    pb, pv = blocked(params, xs, ys), vmapped(params, xs, ys)
    for a, b in zip(jax.tree_util.tree_leaves(pv),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


def test_bf16_stacked_epoch_master_roundtrip_and_loss():
    """The epoch-boundary bf16 scheme (cast once per epoch, f32 gradient
    accumulator, master - lr·Σg) must keep an f32 master and land within
    a small loss band of the f32 trajectory after several epochs — the
    regression pin for the master-param round-trip fix."""
    params, xs, ys = _epoch_fixture()
    x_eval = xs.reshape(xs.shape[0], -1, 28, 28, 1)
    y_eval = ys.reshape(ys.shape[0], -1)

    def cohort_loss(p):
        logits = cnn_mod.forward_im2col_k(p, x_eval)
        return float(jnp.mean(jax.vmap(cross_entropy)(logits, y_eval)))

    f32_fn = jax.jit(make_stacked_epoch_fn(ForwardPolicy(), 0.02))
    bf_fn = jax.jit(make_stacked_epoch_fn(
        ForwardPolicy(precision="bf16"), 0.02))
    p32 = pbf = params
    for _ in range(10):
        p32, pbf = f32_fn(p32, xs, ys), bf_fn(pbf, xs, ys)
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(pbf))
    loss0, l32, lbf = cohort_loss(params), cohort_loss(p32), cohort_loss(pbf)
    assert l32 < 0.8 * loss0 and lbf < 0.8 * loss0, (loss0, l32, lbf)
    assert abs(lbf - l32) < 0.15, (l32, lbf)


def test_stacked_epoch_donates_stacked_carry():
    """The stacked ``(K, ...)`` parameter carry must donate through the
    blocked epoch: same buffer in and out, no per-epoch model copy."""
    params, xs, ys = _epoch_fixture(k=4, steps=2, bs=5)
    fn = jax.jit(make_stacked_epoch_fn(ForwardPolicy(), 0.01),
                 donate_argnums=(0,))
    leaf = params["fc1"]["w"]
    ptr0 = leaf.unsafe_buffer_pointer()
    out = fn(params, xs, ys)
    jax.block_until_ready(out)
    assert leaf.is_deleted(), "stacked carry was not donated"
    assert out["fc1"]["w"].unsafe_buffer_pointer() == ptr0, \
        "donated stacked buffer was not aliased to the output"
    assert out["fc1"]["w"].shape == (4,) + tuple(cnn_mod.init_cnn(
        jax.random.PRNGKey(0))["fc1"]["w"].shape)


# -- the fold of the patch cotangent (conv backward's input gradient) ---------

def _fold3x3_scatter_add(dpatches):
    """The scatter-add form ``ref.fold3x3`` replaced: each tap added into
    a zero (B,H+2,W+2,C) buffer, then the border cropped."""
    b, h, w, c9 = dpatches.shape
    c = c9 // 9
    dxp = jnp.zeros((b, h + 2, w + 2, c), dpatches.dtype)
    for t in range(9):
        i, j = divmod(t, 3)
        dxp = dxp.at[:, i:i + h, j:j + w, :].add(
            dpatches[..., t * c:(t + 1) * c])
    return dxp[:, 1:1 + h, 1:1 + w, :]


FOLD_SHAPES = {"conv2_k10": (10 * 10, 14, 14, 72),   # K·B users' conv2
               "conv2_k3": (3 * 10, 14, 14, 72),
               "conv1": (10, 28, 28, 9)}


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("shape", list(FOLD_SHAPES), ids=str)
def test_fold3x3_is_the_scatter_add_bitwise(shape, precision):
    """The gather adds the same terms in the same order.  (In bf16 this
    holds where each add rounds, as on the CPU; a TPU fusion keeps the
    bf16 sum in f32 and rounds once.)"""
    from repro.kernels.fused_cnn.ref import fold3x3
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    g = jax.random.normal(jax.random.PRNGKey(5), FOLD_SHAPES[shape]).astype(dt)
    got = np.asarray(jax.jit(fold3x3)(g))
    want = np.asarray(jax.jit(_fold3x3_scatter_add)(g))
    assert got.dtype == want.dtype and got.shape == want.shape
    bits = np.uint16 if precision == "bf16" else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))


@pytest.mark.parametrize("shape", [(4, 14, 14, 8), (3, 28, 28, 1)], ids=str)
def test_fold3x3_is_the_transpose_of_patches3x3(shape):
    """<patches3x3(x), g> == <x, fold3x3(g)>: the fold is the adjoint."""
    from repro.kernels.fused_cnn.ref import fold3x3, patches3x3
    kx, kg = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(kx, shape)
    g = jax.random.normal(kg, shape[:-1] + (9 * shape[-1],))
    lhs = np.vdot(np.asarray(patches3x3(x), np.float64),
                  np.asarray(g, np.float64))
    rhs = np.vdot(np.asarray(x, np.float64),
                  np.asarray(fold3x3(g), np.float64))
    scale = np.abs(np.asarray(g, np.float64)).sum()
    assert abs(lhs - rhs) <= 1e-6 * scale, (lhs, rhs)
