"""The fused round's profiler spans and device scopes.

``HSFLSimulation._run_round_fused`` opens the host spans ``hsfl.round``,
``hsfl.schedule``, ``hsfl.presample``, ``hsfl.launch`` and
``hsfl.readback``; the next round's schedule and presample run inside a
round, between its launch and its readback.  ``build_fused_round`` names
its device work ``local_epoch``, ``probe`` and ``aggregate``.  The chip
benchmark's per-layer readers match the span names and stats in a
profiler trace, so the tests pin them as a CPU trace records them; the
scopes are pinned in the compiled program's ``op_name`` metadata, where
they reach.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.fused_round import build_fused_round
from repro.core.hsfl import HSFLConfig, HSFLSimulation, _k_bucket
from repro.kernels.fused_cnn.ops import ForwardPolicy
from repro.models.cnn import init_cnn

# round 1 prepares itself, later rounds launch what the one before staged;
# each prepares the next round before it reads its own stats back
FIRST = ["hsfl.schedule", "hsfl.presample", "hsfl.launch", "hsfl.schedule",
         "hsfl.presample", "hsfl.readback"]
LATER = ["hsfl.launch", "hsfl.schedule", "hsfl.presample", "hsfl.readback"]


def small_cfg(**kw):
    # seed 3 at tau_max 5 selects 3 of 12 UAVs a round: K pads to 4
    base = dict(n_uavs=12, k_select=4, n_train=800, n_test=200,
                steps_per_epoch=2, local_epochs=6, seed=3, tau_max=5.0)
    base.update(kw)
    return HSFLConfig(**base)


def traced_rounds(cfg, tmp_path, rounds=3, monkeypatch=None):
    """Run ``rounds`` fused rounds under a profiler trace; return the
    logs, the bytes handed to ``jax.device_put`` for each round's inputs
    (those put after a round's schedule and before the next one's) and
    the ``hsfl.*`` host events as (name, start, end, stats)."""
    sim = HSFLSimulation(cfg)
    staged = []
    put, schedule = jax.device_put, sim._schedule_round

    def counting_put(x, *a, **k):
        staged[-1] += sum(np.asarray(leaf).nbytes
                          for leaf in jax.tree_util.tree_leaves(x))
        return put(x, *a, **k)

    def counting_schedule():
        staged.append(0)
        return schedule()

    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(sim, "_schedule_round", counting_schedule)
    logs, carry = [], None
    with jax.profiler.trace(str(tmp_path)):
        for t in range(1, rounds + 1):
            log, carry = sim.run_round(t, carry)
            logs.append(log)
    monkeypatch.undo()
    # the last round prepared one more, which never runs
    staged = staged[:rounds]
    pd = ProfileData.from_file(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                         recursive=True)[0])
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("hsfl.")]
    return logs, staged, sorted(events, key=lambda e: e[1])


def children(events, parent):
    _, s, e, _ = parent
    return [ev for ev in events
            if ev is not parent and ev[1] >= s and ev[2] <= e]


@pytest.mark.parametrize("codec", [False, True], ids=["plain", "codec"])
def test_round_spans_nest_in_order_with_their_stats(codec, tmp_path,
                                                    monkeypatch):
    cfg = small_cfg(use_delta_codec=codec)
    logs, staged, events = traced_rounds(cfg, tmp_path,
                                         monkeypatch=monkeypatch)
    rounds = [ev for ev in events if ev[0] == "hsfl.round"]
    assert len(rounds) == len(logs) == 3
    for rnd, log, nbytes in zip(rounds, logs, staged):
        assert rnd[3] == {"round": log.round}
        inner = children(events, rnd)
        assert [ev[0] for ev in inner] == (FIRST if log.round == 1
                                           else LATER)
        for a, b in zip(inner, inner[1:]):
            assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
        (launch,) = [ev[3] for ev in inner if ev[0] == "hsfl.launch"]
        assert launch["users"] == log.selected > 0
        assert launch["K"] == _k_bucket(log.selected, cfg.k_select)
        assert launch["staged_bytes"] == nbytes > 0
        assert launch["ahead"] == (log.round > 1)
        for ev in inner:
            if ev[0] != "hsfl.launch":
                assert ev[3] == {}
    if not codec:
        assert any(ev[3]["K"] > ev[3]["users"] for ev in events
                   if ev[0] == "hsfl.launch"), "fixture no longer pads K"


def test_round_with_nobody_selected_only_schedules(tmp_path, monkeypatch):
    logs, staged, events = traced_rounds(small_cfg(tau_max=0.5), tmp_path,
                                         rounds=2, monkeypatch=monkeypatch)
    assert [log.selected for log in logs] == [0, 0]
    assert staged == [0, 0]
    assert [ev[0] for ev in events] == ["hsfl.round", "hsfl.schedule"] * 2


# -- device scopes in the compiled round program ------------------------------

K, E, STEPS, B = 2, 2, 1, 2


def _round_program(variant):
    scheme = "async" if variant == "async" else "opt"
    fn = build_fused_round(
        scheme=scheme, local_epochs=E, steps_per_epoch=STEPS, lr=0.01,
        tau_max=9.0, probe_epochs=() if scheme == "async" else (1,),
        async_weight=0.3, use_codec=variant == "codec",
        interpret=jax.default_backend() != "tpu", k_carry=K,
        forward=ForwardPolicy())
    params = init_cnn(jax.random.PRNGKey(0))
    xs = jnp.zeros((E, K, STEPS, B, 28, 28, 1), jnp.float32)
    ys = jnp.zeros((E, K, STEPS, B), jnp.int32)
    chan = {"rates": jnp.ones((E, K)), "outages": jnp.zeros((E, K), bool),
            "payload_bits": jnp.ones(K), "tau_extra0": jnp.zeros(K),
            "final_rate": jnp.ones(K), "final_outage": jnp.zeros(K, bool),
            "train_time": jnp.zeros(K), "valid": jnp.ones(K, bool)}
    if scheme == "async":
        stack = jax.tree_util.tree_map(
            lambda a: jnp.zeros((K,) + a.shape, a.dtype), params)
        return fn, (params, stack, jnp.zeros(K, bool), xs, ys, chan)
    return fn, (params, xs, ys, chan)


@pytest.mark.parametrize("variant", ["plain", "codec", "async"])
def test_compiled_round_carries_the_scopes(variant):
    fn, args = _round_program(variant)
    text = fn.lower(*args).compile().as_text()
    scopes = {part for op in re.findall(r'op_name="([^"]*)"', text)
              for part in op.split("/")}
    assert {"local_epoch", "aggregate"} <= scopes
    # async probes nothing, so no op of its program is a probe's
    assert ("probe" in scopes) == (variant != "async")
