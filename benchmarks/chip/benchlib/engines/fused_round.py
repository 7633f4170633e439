"""Closed loop of fused HSFL rounds: one simulation, one round after
another, state carried across rounds, each round paid as a user of the
fused engine pays it (``run_round`` then ``evaluate``, as
``HSFLSimulation.run(eval_every=1)`` does).

The simulated environment (digits, shards, fleet, device compute, batch
draws) comes from the mix's fixed ``environment_seed``, so that every run
trains the same users on the same batches in every round; the run's seed
draws the initial weights, which the benchmark makes and hands to the
program.  Set-up builds the simulation, compiles the round program for
every user bucket K the scheduler can produce and the evaluation, then
drives the first ``check_rounds`` rounds through the window's own call.
Those rounds are what the reference follows; the window continues the
same simulation.
The control plane of every round run, window included, is checked against
the reference's replay of it."""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import compare, flops
from ..program import hsfl_config
from ..reference import cnn as ref_cnn
from ..reference import hsfl as ref_hsfl

IMAGE = (28, 28, 1)
SPANS = ("run_round", "evaluate")


def _bucket(n: int, k_select: int) -> int:
    """The stacked-user width the fused engine dispatches for n selected
    users: n rounded up to even, at most K (0 when nobody is selected)."""
    return min(k_select, 2 * ((n + 1) // 2))


def k_buckets(k_select: int):
    return sorted({_bucket(n, k_select) for n in range(1, k_select + 1)})


class State:
    pass


def _warm(sim, K: int):
    """Compile the round program for K stacked users on inputs of the
    round's own shapes and types (zero data, the global model copied)."""
    c = sim.cfg
    e, s, bs = c.local_epochs, c.steps_per_epoch, c.batch_size
    put = jax.device_put
    xs = put(np.zeros((e, K, s, bs) + IMAGE, np.float32))
    ys = put(np.zeros((e, K, s, bs), np.int32))
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
    params = jax.tree_util.tree_map(lambda a: a.copy(), sim.params)
    jax.block_until_ready(sim._fused(params, xs, ys, chan))


def _round(st, t):
    """The window's call: one fused round and its evaluation."""
    with st.spans.span("run_round", round=t) as attrs:
        log, st.carry = st.sim.run_round(t, st.carry)
        attrs["users"] = log.selected
    with st.spans.span("evaluate"):
        loss, acc = st.sim.evaluate()
    return log, loss, acc


def _counts(log):
    return (log.selected, log.arrived_final, log.used_snapshot, log.delayed,
            log.dropped)


def setup(ctx, warm: bool = True) -> State:
    from repro.core.hsfl import HSFLSimulation

    st = State()
    st.ctx, st.spans = ctx, ctx.spans
    cfg = ctx.config
    if cfg["scheme"] != "opt":
        raise ValueError("the fused-round mix drives scheme 'opt'")
    env = ctx.traffic["environment_seed"]
    with st.spans.span("build"):
        st.sim = HSFLSimulation(hsfl_config(cfg, env))
        st.sim.params = jax.device_put(ref_cnn.init(ctx.seed))
    if warm:
        with st.spans.span("warm"):
            for K in k_buckets(cfg["k_select"]):
                _warm(st.sim, K)
            st.sim.evaluate()
    st.carry = None
    st.params, st.loss, st.acc, st.counts = [], [], [], []
    n_check = ctx.traffic["check_rounds"]
    for t in range(1, n_check + 1):
        log, loss, acc = _round(st, t)
        st.params.append(jax.device_get(st.sim.params))
        st.loss.append(loss)
        st.acc.append(acc)
        st.counts.append(_counts(log))
    st.next_round = n_check + 1
    return st


def window(st, seconds: float) -> dict:
    cfg = st.ctx.config
    per_user = (cfg["local_epochs"] * cfg["steps_per_epoch"]
                * cfg["batch_size"])
    lat, counts, samples = [], [], 0
    t0 = time.perf_counter()
    with st.spans.span("window"):
        while time.perf_counter() - t0 < seconds:
            ts = time.perf_counter()
            log, _, _ = _round(st, st.next_round)
            lat.append(time.perf_counter() - ts)
            counts.append(_counts(log))
            samples += log.selected * per_user
            st.next_round += 1
    window_s = time.perf_counter() - t0
    st.window_counts = counts
    shapes = {k: v["w"].shape for k, v in
              jax.eval_shape(lambda: ref_cnn.init(0)).items()}
    return {"rounds": len(lat), "window_s": window_s, "latency_s": lat,
            "attempted": len(st.counts) + len(lat),
            "train_flops": samples * flops.cnn_train_flops(shapes)}


def end_to_end(res: dict) -> dict:
    return {"sim_rounds_per_s": res["rounds"] / res["window_s"],
            "round_ms_p90": float(np.percentile(res["latency_s"], 90,
                                                method="linear")) * 1e3}


def free(st) -> None:
    """Drop the program's device state before the reference runs."""
    st.sim = st.carry = None


def check(st, workload: str, detail: bool = False) -> dict:
    """The compared numbers; the program's state must be freed already."""
    cfg = st.ctx.config
    runs = st.counts + getattr(st, "window_counts", [])
    ref = ref_hsfl.simulate(cfg, st.ctx.traffic["environment_seed"],
                            len(runs), len(st.params),
                            weight_seed=st.ctx.seed)
    mism = sum(a != tuple(b) for a, b in zip(runs, ref["counts"]))
    last = len(st.params) - 1
    if detail:
        st.detail = {"leaves": [compare.leaf_stats(p, q, ref["p0"]) for p, q
                                in zip(st.params, ref["params"])],
                     "loss": [st.loss, ref["loss"]],
                     "acc": [st.acc, ref["acc"]]}
    return {
        "count_rounds_off": float(mism),
        "update1_gap": compare.worst_leaf_gap(st.params[0], ref["params"][0],
                                              ref["p0"]),
        f"change{last + 1}_gap": compare.worst_leaf_gap(
            st.params[last], ref["params"][last], ref["p0"]),
        "loss_gap": max(abs(a - b) / b for a, b in
                        zip(st.loss, ref["loss"])),
        "acc_gap": max(abs(a - b) for a, b in zip(st.acc, ref["acc"])),
    }
