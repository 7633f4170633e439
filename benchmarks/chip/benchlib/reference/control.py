"""The round's control plane in float64 numpy: channel (eqs. 1-7), UAV
mobility and interruptions, device compute, latency (eqs. 9-14) and the
greedy energy-per-sample user selection (Alg. 1, lines 3-5).

The fleet draws from ``default_rng(seed + 1)`` and the device compute from
``default_rng(seed)``, in the order the round consumes them; the round's
batch draws continue the second stream.  Everything else is arithmetic on
the configuration's constants (``cfg["channel"]``, ``cfg["fixed"]``)."""
from __future__ import annotations

import math

import numpy as np

C_LIGHT = 299_792_458.0


def dbm_to_watt(dbm):
    return 10.0 ** (dbm / 10.0) * 1e-3


def rate_bps(pos: np.ndarray, k_db: np.ndarray, ch: dict) -> np.ndarray:
    """Shannon rate of each UAV at ``pos`` with Rician factor ``k_db``:
    Friis free-space loss plus the LOS-probability-weighted excess loss,
    times the expected Rician amplitude, over thermal noise in the band."""
    dz = pos[:, 2] - ch["bs_height_m"]
    dist = np.sqrt(pos[:, 0] ** 2 + pos[:, 1] ** 2 + dz ** 2)
    theta = np.degrees(np.arcsin(np.abs(dz) / np.maximum(dist, 1e-6)))
    plos = 1.0 / (1.0 + ch["a0"] * np.exp(-ch["b0"] * (theta - ch["a0"])))
    d = np.maximum(dist, 1.0)
    fspl = 20.0 * np.log10(4.0 * np.pi * d * ch["carrier_hz"] / C_LIGHT)
    lo = min(ch["eta_los_db"], ch["eta_nlos_db"])
    hi = max(ch["eta_los_db"], ch["eta_nlos_db"])
    loss_db = -fspl - (plos * lo + (1.0 - plos) * hi)
    k = 10.0 ** (k_db / 10.0)
    amp = np.sqrt(k / (k + 1.0)) + np.sqrt(1.0 / (2.0 * (k + 1.0)))
    gain = 10.0 ** (loss_db / 10.0) * amp
    bw = ch["bandwidth_uav_hz"]
    noise = dbm_to_watt(ch["noise_dbm_per_hz"] + 10.0 * np.log10(bw))
    return bw * np.log2(1.0 + gain * dbm_to_watt(ch["p_uav_dbm"]) / noise)


class Fleet:
    """UAVs flying random directions inside the cell, with a two-state
    (Gilbert-Elliott) interruption chain whose stationary share is the
    paper's 30 %."""

    def __init__(self, n: int, ch: dict, fixed: dict, seed: int):
        self.n, self.ch, self.fixed = n, ch, fixed
        self.rng = np.random.default_rng(seed)
        r = ch["cell_radius_m"] * np.sqrt(self.rng.random(n))
        ang = self.rng.random(n) * 2 * np.pi
        z = self.rng.uniform(*ch["uav_z_range"], n)
        self.pos = np.stack([r * np.cos(ang), r * np.sin(ang), z], axis=-1)
        self.k_db = self.rng.uniform(*ch["k_db_range"], n)
        self.bad = self.rng.random(n) < ch["outage_prob"]
        stay = min(max(ch["outage_persistence"], 0.0), 1.0)
        go = ch["outage_prob"] * (1.0 - stay) / max(1.0 - ch["outage_prob"],
                                                      1e-9)
        self.go_bad, self.stay_bad = min(max(go, 0.0), 1.0), stay

    def new_fading(self):
        self.k_db = self.rng.uniform(*self.ch["k_db_range"], self.n)

    def move(self):
        step = self.rng.standard_normal((self.n, 3))
        step /= np.maximum(np.linalg.norm(step, axis=-1, keepdims=True), 1e-9)
        self.pos = self.pos + step * self.fixed["speed_mps"] \
            * self.fixed["epoch_seconds"]
        rad = np.linalg.norm(self.pos[:, :2], axis=-1)
        over = rad > self.ch["cell_radius_m"]
        if over.any():
            self.pos[over, :2] *= (self.ch["cell_radius_m"] / rad[over])[:, None]
        self.pos[:, 2] = np.clip(self.pos[:, 2], *self.ch["uav_z_range"])

    def rates(self):
        return rate_bps(self.pos, self.k_db, self.ch)

    def outages(self):
        u = self.rng.random(self.n)
        self.bad = np.where(self.bad, u < self.stay_bad, u < self.go_bad)
        return self.bad.copy()


def user_costs(rate0: float, flops: float, samples: int, cfg: dict,
               model_bytes: float, ue_bytes: float):
    """(FL latency, SL latency, FL energy, SL energy, FL train s, SL train s)
    of one user under the relaxed b-transmission uplink (eqs. 9-13)."""
    f = cfg["fixed"]
    e, b = cfg["local_epochs"], cfg["b"]
    r0 = max(rate0, 1e-9)
    tt_fl = e * samples * f["flops_per_sample"] / flops
    tt_sl = e * samples * (f["ue_fraction"] * f["flops_per_sample"] / flops
                           + (1 - f["ue_fraction"]) * f["flops_per_sample"]
                           / f["server_flops_per_s"])
    act = f["act_bytes_per_sample"] * samples
    up_fl = b * model_bytes * 8.0 / r0
    up_sl = (b * ue_bytes + act) * 8.0 / r0
    dl_sl = (ue_bytes + act) * 8.0 / max(f["bs_rate_bps"], 1e-9)
    ue_t = e * samples * f["ue_fraction"] * f["flops_per_sample"] / flops
    return (tt_fl + up_fl, tt_sl + up_sl + dl_sl,
            tt_fl * f["power_compute_w"] + up_fl * f["power_tx_w"],
            ue_t * f["power_compute_w"] + up_sl * f["power_tx_w"],
            tt_fl, tt_sl)


def select(rates0, flops, samples, cfg: dict, model_bytes: float,
           ue_bytes: float):
    """Greedy selection: users with a mode inside tau_max, ranked by samples
    per joule at their cheaper mode (stable, best first), each given its
    cheaper feasible mode, SL slots capped at half of K.  Returns a list of
    (user, is_sl, rate0, train_seconds)."""
    k = cfg["k_select"]
    cands = []
    for i in range(len(rates0)):
        fl, sl, fl_en, sl_en, tt_fl, tt_sl = user_costs(
            float(rates0[i]), float(flops[i]), int(samples[i]), cfg,
            model_bytes, ue_bytes)
        opts = []
        if fl <= cfg["tau_max"]:
            opts.append((fl_en, False, tt_fl))
        if sl <= cfg["tau_max"]:
            opts.append((sl_en, True, tt_sl))
        if opts:
            cands.append((i, opts))
    cands.sort(key=lambda c: samples[c[0]] / max(min(o[0] for o in c[1]),
                                                  1e-9), reverse=True)
    out, sl_used = [], 0
    for i, opts in cands:
        if len(out) == k:
            break
        for en, is_sl, tt in sorted(opts, key=lambda o: o[0]):
            if is_sl and sl_used >= k // 2:
                continue
            out.append((i, is_sl, float(rates0[i]), tt))
            sl_used += is_sl
            break
    return out


def probe_epochs(e: int, b: int):
    """Alg. 2, line 12: the b - 1 intermediate epochs e_t % (e/b) == 0."""
    if b <= 1:
        return ()
    period = max(1, round(e / b))
    return tuple(j * period for j in range(1, b) if j * period < e)


def epoch_indices(n: int, steps: int, bs: int, rng) -> np.ndarray:
    """One epoch's (steps, bs) sample indices: fresh permutations of the
    client's shard, concatenated until there are enough."""
    need = steps * bs
    idx = rng.permutation(n)
    while len(idx) < need:
        idx = np.concatenate([idx, rng.permutation(n)])
    return idx[:need].reshape(steps, bs)


def codec_ratio(n: int, block: int, bits: int) -> float:
    """Wire bytes of an n-value int-``bits`` snapshot, one f32 scale per
    ``block`` values, over its f32 bytes."""
    blocks = math.ceil(n / block)
    return (blocks * block * bits / 8.0 + blocks * 4) / (4.0 * n)
