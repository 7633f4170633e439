"""One OPT-HSFL simulation of a language model over the UAV fleet (Algs.
1 and 2), with the control plane of ``hsfl.simulate``: new Rician factors,
greedy selection, e local epochs with the fleet moving and the
interruption chain stepping once per epoch, a snapshot at each scheduled
epoch if the link is up and its delay fits the remaining allowance, the
final upload if the link is up and training plus upload fit tau_max, and
the server's plain mean over the finals that arrived and the snapshots
that stand in for the finals that did not.

Users are trained one at a time from the global weights, which stay on
the host with the running sum, so that the chip holds one user's weights,
its gradient and one sequence's activations at the cut's sizes.  A user
whose final arrives contributes its final weights, one whose final is
lost and whose probe went out its weights after the last such probe;
the others are not trained.  Products run at float32 ``highest``."""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from . import control, moonlight, tokens

F32 = np.float32


def model_config(cfg: dict) -> dict:
    keys = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
            "rms_norm_eps", "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "first_k_dense_replace",
            "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "vocab_size", "layers", "experts_held",
            "expert_offset")
    return {k: cfg[k] for k in keys}


def simulate(cfg: dict, seed: int, n_rounds: int, train_rounds: int,
             weight_seed: int) -> dict:
    """Counts of every round; weights after round 1 and after round
    ``train_rounds``, held-out loss and accuracy after each of the first
    ``train_rounds``, and for each of those rounds ``held``: the (token,
    slot) pairs each held expert took, per user, local epoch and MoE
    layer, with the mask of the epochs trained (users by selection order;
    a user is trained up to the epoch its contribution is taken at).
    ``seed`` draws the environment (token shards, fleet, device compute,
    batches, the correction bias); ``weight_seed`` the initial weights."""
    if cfg["scheme"] != "opt" or cfg.get("schedule_override"):
        raise ValueError("the reference implements scheme 'opt' with the "
                         "paper's probe schedule")
    c = model_config(cfg)
    e, steps, bs = cfg["local_epochs"], cfg["steps_per_epoch"], \
        cfg["batch_size"]
    samples, pools, held = tokens.environment(cfg, seed)
    rng = np.random.default_rng(seed)
    flops = [float(rng.uniform(*cfg["flops_range"]))
             for _ in range(cfg["n_uavs"])]
    fleet = control.Fleet(cfg["n_uavs"], cfg["channel"], cfg["fixed"],
                          seed + 1)
    bias = moonlight.correction_bias(seed, c)
    glob = jax.device_get(moonlight.init(weight_seed, c))
    model_b = cfg["model_bytes"] * cfg["compress_ratio"]
    ue_b = model_b * cfg["ue_model_fraction"]
    probes = control.probe_epochs(e, cfg["b"])
    tau_max = F32(cfg["tau_max"])
    step = jax.jit(partial(moonlight.sgd_step, c=c, lr=cfg["lr"]),
                   donate_argnums=0)
    ev = jax.jit(partial(moonlight.evaluate, c=c))
    out = {"p0": glob, "counts": [], "params": [], "loss": [], "acc": [],
           "held": []}
    n_layers = c["layers"] - c["first_k_dense_replace"]

    for t in range(1, n_rounds + 1):
        fleet.new_fading()
        sched = control.select(fleet.rates(), flops, samples, cfg,
                               model_b, ue_b)
        n = len(sched)
        held_t = (np.zeros((n, e, n_layers, c["experts_held"]), np.int64),
                  np.zeros((n, e), bool))
        if n == 0:
            out["counts"].append((0, 0, 0, 0, 0))
        else:
            sel = np.array([u for u, _, _, _ in sched], int)
            rates = np.zeros((e, n), F32)
            outs = np.zeros((e, n), bool)
            idx = []
            for ep in range(e):
                fleet.move()
                r, o = fleet.rates(), fleet.outages()
                rates[ep], outs[ep] = r[sel], o[sel]
                idx.append([control.epoch_indices(samples[u], steps, bs,
                                                  rng) for u in sel])
            f_rate = fleet.rates()[sel].astype(F32)
            f_out = fleet.outages()[sel]
            payload = np.array([(ue_b if sl else model_b)
                                for _, sl, _, _ in sched])
            bits = (payload * 8.0).astype(F32)
            tau_extra = ((cfg["b"] - 1) * payload * 8.0
                         / np.maximum([r0 for _, _, r0, _ in sched], 1e-9)
                         ).astype(F32)
            snap_epoch = np.zeros(n, int)
            for ep in probes:
                tau = bits / np.maximum(rates[ep - 1], F32(1e-9))
                ok = ~outs[ep - 1] & (tau <= tau_extra)
                tau_extra = np.where(ok, tau_extra - tau, tau_extra)
                snap_epoch = np.where(ok, ep, snap_epoch)
            train_s = np.array([tt for _, _, _, tt in sched], F32)
            arrived = ~f_out & (train_s + bits / np.maximum(f_rate, F32(1e-9))
                                <= tau_max)
            rescued = ~arrived & (snap_epoch > 0)
            out["counts"].append((n, int(arrived.sum()), int(rescued.sum()),
                                  0, int(n - arrived.sum() - rescued.sum())))
            if t <= train_rounds and (arrived | rescued).any():
                stop = np.where(arrived, e, snap_epoch)
                glob = _train_round(glob, bias, pools, sel, idx, stop, step,
                                    held_t[0])
                held_t[1][:] = np.arange(e) < stop[:, None]
        if t <= train_rounds:
            out["held"].append(held_t)
            if t in (1, train_rounds):
                out["params"].append(glob)
            loss, acc = ev(jax.device_put(glob), bias, x=held[:, :-1],
                           y=held[:, 1:])
            out["loss"].append(float(loss))
            out["acc"].append(float(acc))
    return out


def _train_round(glob, bias, pools, sel, idx, stop, step, held):
    """The mean over the users with ``stop[j] > 0`` of their weights after
    ``stop[j]`` local epochs from the global weights; each epoch's tokens
    per held expert are added into ``held[j, epoch]``."""
    total, k = None, 0
    for j, u in enumerate(sel):
        if stop[j] == 0:
            continue
        p = jax.device_put(glob)
        for ep in range(stop[j]):
            for rows in idx[ep][j]:
                seqs = pools[u][rows % len(pools[u])]
                p, h = step(p, bias, x=seqs[:, :-1], y=seqs[:, 1:])
                held[j, ep] += np.asarray(h)
        p = jax.device_get(p)
        total = p if total is None else jax.tree_util.tree_map(
            np.add, total, p)
        k += 1
    return jax.tree_util.tree_map(lambda a: a / F32(k), total)
