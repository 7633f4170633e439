"""One OPT-HSFL simulation, round by round (Algs. 1 and 2).

Each round: new Rician factors, greedy selection, e local epochs in
lockstep with the fleet moving and the interruption chain stepping once per
epoch, an opportunistic snapshot at each scheduled epoch if the channel is
up and its delay m/r (eq. 15) fits the remaining allowance (eqs. 14, 16),
the final upload at the end if the link is up and training plus upload fit
tau_max, and the server's plain mean over the finals that arrived and the
snapshots that stand in for the finals that did not.

The per-user decisions are made in float32, the precision in which the
round program states them; selection and costs are float64.  The CNN's
products take the configuration's ``matmul_precision``.  Training and
evaluation run only for the first ``train_rounds`` rounds; the later rounds
replay the control plane and its random draws alone, since no decision of
the round depends on the weights."""
from __future__ import annotations

import jax
import numpy as np

from . import cnn, codec, control, digits

F32 = np.float32


def simulate(cfg: dict, seed: int, n_rounds: int, train_rounds: int,
             weight_seed: int | None = None) -> dict:
    """Counts of every round, and weights, test loss and accuracy after
    each of the first ``train_rounds``.  ``seed`` draws the environment
    (data, shards, fleet, device compute, batches); ``weight_seed``, where
    given, draws the initial weights in its place."""
    if cfg["scheme"] != "opt" or cfg.get("schedule_override"):
        raise ValueError("the reference implements scheme 'opt' with the "
                         "paper's probe schedule")
    e, steps, bs = cfg["local_epochs"], cfg["steps_per_epoch"], \
        cfg["batch_size"]
    clients, (test_x, test_y) = digits.environment(cfg, seed)
    rng = np.random.default_rng(seed)
    flops = [float(rng.uniform(*cfg["flops_range"]))
             for _ in range(cfg["n_uavs"])]
    samples = [len(c[1]) for c in clients]
    fleet = control.Fleet(cfg["n_uavs"], cfg["channel"], cfg["fixed"],
                          seed + 1)
    params = cnn.init(seed if weight_seed is None else weight_seed)
    p0 = jax.device_get(params)
    ratio = (control.codec_ratio(cnn.param_count(params), cfg["codec_block"],
                                 cfg["codec_bits"])
             if cfg["use_delta_codec"] else cfg["compress_ratio"])
    model_b = cfg["model_bytes"]
    ue_b = model_b * cfg["ue_model_fraction"]
    probes = control.probe_epochs(e, cfg["b"])
    tau_max = F32(cfg["tau_max"])
    out = {"p0": p0, "counts": [], "params": [], "loss": [], "acc": [],
           "trained_users": []}

    for t in range(1, n_rounds + 1):
        fleet.new_fading()
        sched = control.select(fleet.rates(), flops, samples, cfg,
                               model_b * ratio, ue_b * ratio)
        n = len(sched)
        if n == 0:                 # nothing to train, send or draw
            out["counts"].append((0, 0, 0, 0, 0))
            if t <= train_rounds:
                _record(out, params, test_x, test_y, 0, cfg)
            continue
        sel = np.array([u for u, _, _, _ in sched], int)
        rates = np.zeros((e, n), F32)
        outs = np.zeros((e, n), bool)
        idx = []
        for ep in range(e):
            fleet.move()
            r, o = fleet.rates(), fleet.outages()
            rates[ep], outs[ep] = r[sel], o[sel]
            idx.append([control.epoch_indices(samples[u], steps, bs, rng)
                        for u in sel])
        f_rate, f_out = fleet.rates()[sel].astype(F32), fleet.outages()[sel]

        payload = np.array([(ue_b if sl else model_b) for _, sl, _, _
                            in sched]) * ratio
        bits = (payload * 8.0).astype(F32)
        tau_extra = ((cfg["b"] - 1) * payload * 8.0
                     / np.maximum([r0 for _, _, r0, _ in sched], 1e-9)
                     ).astype(F32)
        sent = np.zeros(n, bool)
        snap_epoch = np.zeros(n, int)
        for ep in probes:
            tau = bits / np.maximum(rates[ep - 1], F32(1e-9))
            ok = ~outs[ep - 1] & (tau <= tau_extra)
            tau_extra = np.where(ok, tau_extra - tau, tau_extra)
            snap_epoch = np.where(ok, ep, snap_epoch)
            sent |= ok
        train_s = np.array([tt for _, _, _, tt in sched], F32)
        arrived = ~f_out & (train_s + F32(0.0)
                            + bits / np.maximum(f_rate, F32(1e-9))
                            <= tau_max)
        rescued = ~arrived & sent
        out["counts"].append((n, int(arrived.sum()), int(rescued.sum()), 0,
                              int(n - arrived.sum() - rescued.sum())))

        if t > train_rounds:
            continue
        if (arrived | rescued).any():
            params = _train_round(params, clients, sel, idx, arrived,
                                  snap_epoch, cfg)
        _record(out, params, test_x, test_y, n, cfg)
    return out


def _record(out, params, test_x, test_y, n, cfg):
    out["trained_users"].append(n)
    out["params"].append(jax.device_get(params))
    loss, acc = cnn.evaluate(params, test_x, test_y,
                             precision=cfg["matmul_precision"])
    out["loss"].append(float(loss))
    out["acc"].append(float(acc))


def _train_round(glob, clients, sel, idx, arrived, snap_epoch, cfg):
    """Local training of the selected users from the global model, then the
    server's mean over finals and the snapshots that rescue missed ones."""
    k = len(sel)
    stack = jax.tree_util.tree_map(
        lambda a: np.broadcast_to(np.asarray(a), (k,) + a.shape), glob)
    snaps = {}
    for ep in range(cfg["local_epochs"]):
        xs = np.stack([clients[u][0][idx[ep][j]] for j, u in enumerate(sel)])
        ys = np.stack([clients[u][1][idx[ep][j]] for j, u in enumerate(sel)])
        stack = cnn.epoch_users(stack, xs, ys, cfg["lr"],
                                precision=cfg["matmul_precision"])
        for j in np.flatnonzero(snap_epoch == ep + 1):
            snaps[j] = jax.tree_util.tree_map(lambda a: a[j], stack)
    contrib = []
    for j in range(k):
        if arrived[j]:
            contrib.append(jax.tree_util.tree_map(lambda a: a[j], stack))
        elif j in snaps:
            s = snaps[j]
            if cfg["use_delta_codec"]:
                s = codec.roundtrip(s, glob, cfg["codec_block"],
                                    cfg["codec_bits"])
            contrib.append(s)
    return jax.tree_util.tree_map(lambda *ls: sum(ls) / len(ls), *contrib)
