"""The streamed round: users trained one after another inside one round
program and folded into a running sum (``build_fused_round(streamed=
True)``), against the stacked round on the CNN and on a tiny Moonlight
(DeepSeek-V3) model, and ``HSFLSimulation`` on that model against a round
of the plain float32 reference (``models/moonlight_ref.py``)."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from moonlight_tiny import params as tiny_params
from moonlight_tiny import TINY_KEYS, ref_config, register_tiny, tiny
from repro.core import fused_round
from repro.core.fused_round import build_fused_round, streams_users
from repro.core.hsfl import (HSFLConfig, HSFLSimulation, init_params,
                             model_compress_ratio, shard_sizes)
from repro.data.partition import partition
from repro.data.synthetic import Dataset, make_digits
from repro.kernels.fused_cnn.ops import ForwardPolicy
from repro.models import moonlight_ref as ref
from repro.models.registry import client_model
from repro.models.cnn import init_cnn

E, PROBES = 3, (2,)
ROUND = dict(scheme="opt", local_epochs=E, steps_per_epoch=1, lr=0.05,
             tau_max=9.0, probe_epochs=PROBES)


def _chan(K, n):
    """n of K slots valid: user 0 arrives, user 1 misses its final but
    its probe went out (rescued), user 2 misses both (dropped)."""
    valid = np.arange(K) < n
    return {
        "rates": jnp.full((E, K), 1e7, jnp.float32),
        "outages": jnp.asarray(np.tile([False, False, True] + [False] * (K - 3),
                                       (E, 1))[:, :K]),
        "payload_bits": jnp.full((K,), 8e6, jnp.float32),
        "tau_extra0": jnp.full((K,), 2.0, jnp.float32),
        "final_rate": jnp.full((K,), 1e7, jnp.float32),
        "final_outage": jnp.asarray(np.arange(K) % 3 != 0),
        "train_time": jnp.full((K,), 1.0, jnp.float32),
        "valid": jnp.asarray(valid),
    }


def _both(params, xs, ys, chan, **kw):
    stacked = build_fused_round(**ROUND, **kw)
    streamed = build_fused_round(**ROUND, streamed=True, **kw)
    copy = lambda t: jax.tree_util.tree_map(jnp.array, t)  # noqa: E731
    a = stacked(copy(params), xs, ys, chan)
    b = streamed(copy(params), xs, ys, chan)
    return a, b, streamed


def _same(a, b, rtol):
    (pa, sa), (pb, sb, _) = a, b
    for f in sa._fields:
        np.testing.assert_array_equal(getattr(sa, f), getattr(sb, f), f)
    for x, y in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=rtol * 1e-2)


def test_streamed_matches_stacked_on_the_cnn():
    K = 4
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((E, K, 1, 4, 28, 28, 1)),
                     jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, (E, K, 1, 4)), jnp.int32)
    a, b, _ = _both(init_cnn(jax.random.PRNGKey(0)), xs, ys, _chan(K, 3),
                    forward=ForwardPolicy())
    _same(a, b, 1e-5)
    st = b[1]
    assert np.asarray(st.arrived).tolist() == [True, False, False, False]
    assert np.asarray(st.rescued).tolist() == [False, True, False, False]
    assert np.asarray(st.dropped).tolist() == [False, False, True, False]


def test_streamed_matches_stacked_on_the_tiny_model():
    cfg = tiny()
    params = tiny_params(cfg)
    model = client_model(cfg, 1)
    K, S = 3, 8
    toks = np.random.default_rng(1).integers(0, 256, (E, K, 1, 2, S + 1))
    xs, ys = jnp.asarray(toks[..., :-1]), jnp.asarray(toks[..., 1:])
    stacked = build_fused_round(**ROUND, forward=model.logits)
    streamed = build_fused_round(
        **ROUND, streamed=True, client_step=model.loss_and_grad,
        load0=model.zero_load)
    copy = lambda t: jax.tree_util.tree_map(jnp.array, t)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        a = stacked(copy(params), xs, ys, _chan(K, 3))
        b = streamed(copy(params), xs, ys, _chan(K, 3))
    _same(a, b, 1e-4)
    # per user slot and epoch: of the (token, slot) pairs of a step (2 x 8
    # tokens, 3 slots, 2 MoE layers), the 4 held experts took some
    held = np.asarray(b[2]["held"])
    assert held.shape == (K, E, 2, 4)
    per_step = held.sum(axis=(2, 3))
    assert (per_step > 0).all() and (per_step <= 16 * 3 * 2).all()


def test_one_program_serves_every_user_count():
    K = 4
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.standard_normal((E, K, 1, 2, 28, 28, 1)),
                     jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, (E, K, 1, 2)), jnp.int32)
    fn = build_fused_round(**ROUND, streamed=True, forward=ForwardPolicy())
    params = init_cnn(jax.random.PRNGKey(1))
    for n in (0, 1, 4):
        p = jax.tree_util.tree_map(jnp.array, params)
        new, stats, _ = fn(p, xs, ys, _chan(K, n))
        moved = any(not np.array_equal(x, y) for x, y in zip(
            jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(params)))
        assert moved == (n > 0)
    assert fn._cache_size() == 1


@pytest.mark.parametrize("scheme", ["async", "opt_trimmed", "opt_median",
                                    "opt_clip"])
def test_streamed_round_refuses_whole_cohort_schemes(scheme):
    with pytest.raises(ValueError, match="at once"):
        build_fused_round(**dict(ROUND, scheme=scheme), streamed=True,
                          k_carry=4, forward=ForwardPolicy())


def test_streamed_round_refuses_the_codec():
    with pytest.raises(ValueError, match="codec"):
        build_fused_round(**ROUND, streamed=True, use_codec=True,
                          forward=ForwardPolicy())


def test_path_is_chosen_from_tree_bytes_and_device_memory(monkeypatch):
    monkeypatch.setattr(fused_round, "device_memory_bytes",
                        lambda device=None: 16 * 10 ** 9)
    cnn = init_cnn(jax.random.PRNGKey(0))
    assert not streams_users(cnn, 10)
    cut = HSFLConfig(model="moonlight-16b-a3b", layers=5, experts_held=8,
                     vocab_size=20480)
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == 568_484_352      # the cut of PERF.md, section 4
    assert streams_users(shapes, 10)


def test_shard_sizes_are_the_digits_split():
    cfg = HSFLConfig(n_uavs=6, n_train=600, n_test=100, seed=5)
    full = make_digits(700, seed=5)
    train = Dataset(full.x[:600], full.y[:600])
    want = [len(c) for c in partition(train, 6, "noniid", 5)]
    assert shard_sizes(cfg) == want


def test_codec_ratio_comes_from_the_configured_tree():
    from repro.kernels.delta_codec.ops import codec_ratio
    cnn = HSFLConfig(use_delta_codec=True)
    assert model_compress_ratio(cnn) == codec_ratio(110_634, 512, 8)
    lm = HSFLConfig(use_delta_codec=True, model="moonlight-16b-a3b",
                    layers=5, experts_held=8, vocab_size=20480)
    assert 0.25 < model_compress_ratio(lm) < model_compress_ratio(cnn)


def test_hsfl_round_on_the_tiny_model_matches_the_reference(monkeypatch,
                                                           tmp_path):
    """One round of ``HSFLSimulation`` (streamed, as the memory rule puts
    a model that does not fit stacked) against the reference trained on
    the same batches under the round's own decisions."""
    monkeypatch.setattr(fused_round, "device_memory_bytes",
                        lambda device=None: 1)
    register_tiny(monkeypatch, "moonlight-tiny", TINY_KEYS)
    cfg = HSFLConfig(model="moonlight-tiny", layers=3, experts_held=4,
                     expert_offset=2, seq_len=8, token_pool=3, eval_seqs=2,
                     batch_size=2, steps_per_epoch=1, local_epochs=E, b=2,
                     n_uavs=12, k_select=4, n_train=800, n_test=200, seed=3,
                     tau_max=5.0, **TINY_KEYS)
    sim = HSFLSimulation(cfg)
    assert sim._streamed
    model = client_model(cfg.model_config().replace(dtype="float32"),
                         cfg.seed)
    sim._fused = build_fused_round(
        **sim._round_kwargs(), streamed=True,
        client_step=model.loss_and_grad, load0=model.zero_load)
    p0 = tiny_params(model.cfg, 4)
    sim.params = jax.tree_util.tree_map(jnp.array, p0)
    seen = {}
    fused = sim._fused

    def spy(p, xs, ys, chan):
        seen.update(xs=np.asarray(xs), ys=np.asarray(ys))
        out = fused(p, xs, ys, chan)
        seen["stats"] = jax.device_get(out[1])
        return out

    sim._fused = spy
    with jax.default_matmul_precision("highest"):
        with jax.profiler.trace(str(tmp_path)):
            log, _ = sim.run_round(1, None)
    st = seen["stats"]
    assert log.selected > 0 and log.arrived_final + log.used_snapshot > 0
    c = ref_config(model.cfg)
    lg = jax.jit(lambda p, x, y: ref.loss_and_grad(p, model.buffers, c, x,
                                                   y))
    contrib = []
    for u in range(log.selected):
        p, snap = p0, None
        for e in range(E):
            for s in range(cfg.steps_per_epoch):
                _, g = lg(p, seen["xs"][e, u, s], seen["ys"][e, u, s])
                p = jax.tree_util.tree_map(lambda w, d: w - cfg.lr * d, p, g)
            if e + 1 in sim._probe_epochs and st.rescued[u]:
                snap = p
        if st.arrived[u]:
            contrib.append(p)
        elif st.rescued[u]:
            contrib.append(snap)
    want = jax.tree_util.tree_map(lambda *ls: sum(ls) / len(ls), *contrib)
    for x, y in zip(jax.tree_util.tree_leaves(sim.params),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-6)
    # the launch's steps and tokens, and the experts span after readback
    pd = ProfileData.from_file(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                         recursive=True)[0])
    ev = {e.name: dict(e.stats) for pl in pd.planes
          if pl.name.startswith("/host:") for ln in pl.lines
          for e in ln.events if e.name.startswith("hsfl.")}
    steps = log.selected * E * cfg.steps_per_epoch
    assert ev["hsfl.launch"]["steps"] == steps
    assert ev["hsfl.launch"]["tokens"] == steps * 2 * 8
    held = sim.expert_load["held"]
    assert held.shape == (cfg.k_select, E, 2, 4)
    assert held[log.selected:].sum() == 0
    assert ev["hsfl.experts"]["routed"] == sim.expert_load["routed"] \
        == held.sum() > 0
    assert ev["hsfl.experts"]["held_max"] >= ev["hsfl.experts"]["held_mean"]
    loss, acc = sim.evaluate()
    assert np.isfinite(loss) and 0.0 <= acc <= 1.0
