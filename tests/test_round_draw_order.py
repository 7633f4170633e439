"""The fused engine stages round t+1 while round t's program runs; the
inputs each launch receives must still be those of the serial order.

A twin simulation of the same configuration calls ``_schedule_round``,
``_presample_round`` and ``_user_consts`` one round after another, as a
round that prepared itself would; the batches, labels and channel arrays
that ``HSFLSimulation._fused`` receives must equal the twin's bit for bit,
round by round, including rounds in which nobody is selected."""
import jax
import numpy as np
import pytest

from moonlight_tiny import TINY_KEYS, register_tiny
from repro.core import fused_round
from repro.core.channel import ChannelParams
from repro.core.hsfl import HSFLConfig, HSFLSimulation, _k_bucket

BASE = dict(n_uavs=12, k_select=4, n_train=800, n_test=200,
            steps_per_epoch=2, local_epochs=6, seed=3, tau_max=5.0)
CASES = {
    "opt": dict(),
    "opt-codec": dict(use_delta_codec=True),
    "async": dict(scheme="async", b=1),
    # a 100 MB payload whose upload takes most of a 4.25 s budget, and a
    # Rician factor that swings 40 dB: rounds 2 and 4 select nobody
    "nobody-between": dict(tau_max=4.25, model_bytes=100e6,
                           channel=ChannelParams(k_db_range=(-20.0, 20.0))),
    "moonlight-streamed": dict(model="moonlight-tiny", layers=3,
                               experts_held=4, expert_offset=2, seq_len=8,
                               token_pool=3, eval_seqs=2, batch_size=2,
                               local_epochs=3, steps_per_epoch=1,
                               **TINY_KEYS),
}
ROUNDS = 4


def serial_inputs(cfg, rounds):
    """What each round of the twin draws, prepared in series: the users
    selected, and (xs, ys, chan) where anyone is."""
    twin = HSFLSimulation(cfg)
    out = []
    for _ in range(rounds):
        sched, ue_bytes = twin._schedule_round()
        if not sched:
            out.append((0, None))
            continue
        K = (cfg.k_select if twin._streamed
             else _k_bucket(len(sched), cfg.k_select))
        K = -(-K // twin._shard_ndev) * twin._shard_ndev
        xs, ys, rates, outs, final_rate, final_out = \
            twin._presample_round(sched, K)
        payload, tau_extra0, train_time, valid = \
            twin._user_consts(sched, ue_bytes, K)
        chan = {"rates": rates, "outages": outs,
                "payload_bits": np.asarray(payload * 8.0, np.float32),
                "tau_extra0": np.asarray(tau_extra0, np.float32),
                "final_rate": final_rate, "final_outage": final_out,
                "train_time": np.asarray(train_time, np.float32),
                "valid": valid}
        out.append((len(sched), (xs, ys, chan)))
    return out


@pytest.mark.parametrize("case", CASES)
def test_staged_ahead_rounds_draw_the_serial_inputs(case, monkeypatch):
    if case.startswith("moonlight"):
        # stream the tiny model, as the memory rule streams the real one
        monkeypatch.setattr(fused_round, "device_memory_bytes",
                            lambda device=None: 1)
        register_tiny(monkeypatch, "moonlight-tiny", TINY_KEYS)
    cfg = HSFLConfig(**{**BASE, **CASES[case]})
    want = serial_inputs(cfg, ROUNDS)
    sim = HSFLSimulation(cfg)
    assert sim._streamed == case.startswith("moonlight")
    seen, fused = [], sim._fused

    def record(*args):
        seen.append(jax.device_get(args[-3:]))
        return fused(*args)

    sim._fused = record
    carry, selected = None, []
    for t in range(1, ROUNDS + 1):
        log, carry = sim.run_round(t, carry)
        selected.append(log.selected)
    assert selected == [n for n, _ in want]
    if case == "nobody-between":
        assert selected[1] == selected[3] == 0 < selected[0] * selected[2]
    staged = [inputs for n, inputs in want if n]
    assert len(seen) == len(staged) > 0
    for got, exp in zip(seen, staged):
        for a, b in zip(got[:2], exp[:2]):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        assert got[2].keys() == exp[2].keys()
        for k, a in got[2].items():
            assert a.dtype == exp[2][k].dtype, k
            np.testing.assert_array_equal(a, exp[2][k], k)
