"""Closed loop of fused HSFL rounds of a language model: one simulation,
one round after another, each paid as a user of the fused engine pays it
(``run_round`` then ``evaluate``, as ``HSFLSimulation.run(eval_every=1)``
does), on the round that streams its users through one program.

The simulated environment (token shards, fleet, device compute, batch
draws, the correction bias) comes from the mix's fixed
``environment_seed``; the run's seed draws the initial weights, which
the benchmark makes (``reference/moonlight.init``) and hands to the
program.  Set-up refuses a program that builds no token model, builds
the simulation, compiles the round program (one for every user count)
and the evaluation, then drives the first ``check_rounds`` rounds through
the window's own call.  Those rounds are what the reference follows; the
window continues the same simulation.  The control plane of every round
run, window included, is checked against the reference's replay of it;
in the first rounds, the (token, slot) pairs the held experts took in
each step the reference trains are checked against the reference's
routing of them."""
from __future__ import annotations

import time
from dataclasses import fields

import jax
import numpy as np

from .. import compare, lm_flops
from ..program import hsfl_config
from ..reference import hsfl_lm
from ..reference import moonlight as ref_lm

SPANS = ("run_round", "evaluate")


class State:
    pass


def _program_builds_the_model(cfg: dict) -> None:
    """Refuse, at once, a program whose ``HSFLConfig`` cannot name the
    configured model (it would build the CNN and ignore the rest)."""
    from repro.core.hsfl import HSFLConfig

    names = {f.name for f in fields(HSFLConfig)}
    missing = sorted({"model", "seq_len", "experts_held", "hidden_size"}
                     - names)
    if missing:
        raise RuntimeError(f"the program cannot build model "
                           f"{cfg['model']!r}: HSFLConfig has no {missing}")


def _round(st, t):
    """The window's call: one fused round and its evaluation."""
    with st.spans.span("run_round", round=t) as attrs:
        log, st.carry = st.sim.run_round(t, st.carry)
        attrs["users"] = log.selected
    with st.spans.span("evaluate"):
        loss, acc = st.sim.evaluate()
    load = st.sim.expert_load if log.selected else None
    return log, loss, acc, load


def _counts(log):
    return (log.selected, log.arrived_final, log.used_snapshot, log.delayed,
            log.dropped)


def _warm(sim) -> None:
    """Compile the round program on inputs of the round's shapes and types
    with no user selected: the weights pass through unchanged."""
    c = sim.cfg
    e, s, bs, K = c.local_epochs, c.steps_per_epoch, c.batch_size, \
        c.k_select
    put = jax.device_put
    toks = put(np.zeros((e, K, s, bs, c.seq_len), np.int32))
    chan = put({
        "rates": np.ones((e, K), np.float32),
        "outages": np.zeros((e, K), bool),
        "payload_bits": np.ones(K, np.float32),
        "tau_extra0": np.zeros(K, np.float32),
        "final_rate": np.ones(K, np.float32),
        "final_outage": np.zeros(K, bool),
        "train_time": np.zeros(K, np.float32),
        "valid": np.zeros(K, bool),
    })
    sim.params, _, load = sim._fused(sim.params, toks, toks, chan)
    jax.block_until_ready(load)


def setup(ctx, warm: bool = True) -> State:
    cfg = ctx.config
    _program_builds_the_model(cfg)
    from repro.core.hsfl import HSFLSimulation

    st = State()
    st.ctx, st.spans = ctx, ctx.spans
    if cfg["scheme"] != "opt":
        raise ValueError("the fused-round mix drives scheme 'opt'")
    env = ctx.traffic["environment_seed"]
    with st.spans.span("build"):
        st.sim = HSFLSimulation(hsfl_config(cfg, env))
        if not st.sim._streamed:
            raise RuntimeError("the round stacks the users of a model that "
                               "the cell sizes to stream")
        st.sim.params = None
        st.sim.params = ref_lm.init(ctx.seed, hsfl_lm.model_config(cfg))
    if warm:
        with st.spans.span("warm"):
            _warm(st.sim)
            st.sim.evaluate()
    st.carry = None
    st.params, st.loss, st.acc, st.counts, st.held = [], [], [], [], []
    n_check = ctx.traffic["check_rounds"]
    for t in range(1, n_check + 1):
        log, loss, acc, load = _round(st, t)
        if t in (1, n_check):
            st.params.append(jax.device_get(st.sim.params))
        st.loss.append(loss)
        st.acc.append(acc)
        st.counts.append(_counts(log))
        st.held.append(load["held"] if load else None)
    st.next_round = n_check + 1
    return st


def window(st, seconds: float) -> dict:
    cfg = st.ctx.config
    c = hsfl_lm.model_config(cfg)
    steps_per_user = cfg["local_epochs"] * cfg["steps_per_epoch"]
    lat, counts, flops = [], [], 0
    t0 = time.perf_counter()
    with st.spans.span("window"):
        while time.perf_counter() - t0 < seconds:
            ts = time.perf_counter()
            log, _, _, load = _round(st, st.next_round)
            lat.append(time.perf_counter() - ts)
            counts.append(_counts(log))
            if load:
                flops += lm_flops.train_flops(
                    c, log.selected * steps_per_user, cfg["batch_size"],
                    cfg["seq_len"], load["routed"])
            st.next_round += 1
    window_s = time.perf_counter() - t0
    st.window_counts = counts
    return {"rounds": len(lat), "window_s": window_s, "latency_s": lat,
            "attempted": len(st.counts) + len(lat), "train_flops": flops}


def end_to_end(res: dict) -> dict:
    return {"sim_rounds_per_s": res["rounds"] / res["window_s"],
            "round_ms_p90": float(np.percentile(res["latency_s"], 90,
                                                method="linear")) * 1e3}


def free(st) -> None:
    """Drop the program's device state before the reference runs."""
    st.sim = st.carry = None


def dropped_pairs(held, ref_held) -> float:
    """The (token, slot) pairs that the reference's held experts took and
    the program's did not, over the steps the reference trains in the
    check rounds: the shortfall of the program's total.  The program's
    bf16 products flip the routing of a few near ties, so sound runs read
    a small shortfall; a float32 program reads 0."""
    prog = ref = 0
    for got, (want, trained) in zip(held, ref_held):
        if not trained.any():
            continue
        prog += int(np.asarray(got)[:len(trained)][trained].sum())
        ref += int(want[trained].sum())
    return float(max(ref - prog, 0))


def check(st, workload: str, detail: bool = False) -> dict:
    """The compared numbers; the program's state must be freed already."""
    cfg = st.ctx.config
    runs = st.counts + getattr(st, "window_counts", [])
    n_check = len(st.counts)
    ref = hsfl_lm.simulate(cfg, st.ctx.traffic["environment_seed"],
                           len(runs), n_check, weight_seed=st.ctx.seed)
    mism = sum(a != tuple(b) for a, b in zip(runs, ref["counts"]))
    if detail:
        st.detail = {"leaves": [compare.leaf_stats(p, q, ref["p0"]) for p, q
                                in zip(st.params, ref["params"])],
                     "loss": [st.loss, ref["loss"]],
                     "acc": [st.acc, ref["acc"]]}
    return {
        "count_rounds_off": float(mism),
        "update1_gap": compare.worst_leaf_gap(st.params[0], ref["params"][0],
                                              ref["p0"]),
        f"change{n_check}_gap": compare.worst_leaf_gap(
            st.params[-1], ref["params"][-1], ref["p0"]),
        "loss_gap": max(abs(a - b) / b for a, b in
                        zip(st.loss, ref["loss"])),
        "acc_gap": max(abs(a - b) for a, b in zip(st.acc, ref["acc"])),
        "dropped_tokens": dropped_pairs(st.held, ref["held"]),
    }
