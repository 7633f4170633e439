"""OpportunisticSync — the paper's OPT scheme as a multi-pod training feature.

Mapping (DESIGN.md §2): FL clients -> pods running local SGD (DiLoCo-style
local training with round-boundary averaging); the UAV's fluctuating air
interface -> the cross-pod DCN/ICI link, modelled by a per-pod, per-step link
rate trace + outage draws; the BS aggregation -> a masked mean over the
``pod`` mesh axis.

Faithful transliteration of Algorithm 2 onto jax.lax control flow:

  inner step e_t:   if e_t % (e/b) == 0:                 (scheduled probe)
                        τ = payload / rate(e_t)          (eq. 15)
                        if τ <= τ_extra and no outage:
                            snapshot <- params;  τ_extra -= τ   (eq. 16)
  round boundary:   contribution_p = arrived_p ? params_p : snapshot_p
                    ω <- Σ_p valid_p · contribution_p / Σ_p valid_p
                    (pods with neither final nor snapshot are excluded —
                     'discard'; 'async' staleness-weighting is the baseline)

State lives in TrainState's snapshot/snapshot_step/tau_extra slots.  All
per-pod state is stacked on a leading pod axis and the functions run under
``shard_map`` over ``axis``; everything is lax.cond/where — no host round
trips inside a round.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.schemes import get_scheme, probe
from repro.core.transmission import schedule_period as _schedule_period
from repro.models import module as m
from repro.training.train_state import TrainState


@dataclass(frozen=True)
class OppSyncConfig:
    inner_steps: int = 6          # e — local steps per communication round
    budget: int = 2               # b — total transmissions per round
    payload: float = 1.0          # normalized model bytes (m_i)
    rate0: float = 1.0            # budgeting rate r⁰ (eq. 14 denominator)
    outage_prob: float = 0.3
    axis: str = "pod"
    scheme: str = "opt"           # opt | discard | async
    async_alpha: float = 0.4
    async_a: float = 0.5

    @property
    def tau_extra0(self) -> float:
        return (self.budget - 1) * self.payload / self.rate0   # eq. (14)

    def schedule_period(self) -> int:
        return _schedule_period(self.inner_steps, self.budget)


def is_scheduled(cfg: OppSyncConfig, inner_step: jnp.ndarray) -> jnp.ndarray:
    """Alg. 2 line 12: e_t % (e/b) == 0, excluding the final step."""
    if cfg.budget <= 1:
        return jnp.zeros((), bool)
    per = cfg.schedule_period()
    return (inner_step % per == 0) & (inner_step < cfg.inner_steps) \
        & (inner_step > 0)


def maybe_snapshot(cfg: OppSyncConfig, state: TrainState,
                   rate: jnp.ndarray, outage: jnp.ndarray) -> TrainState:
    """Opportunistic_Transmission (Alg. 2 lines 17–21), branch-free."""
    inner = state.step % cfg.inner_steps
    ok, tau_extra = probe(is_scheduled(cfg, inner), outage, rate,
                          cfg.payload, state.tau_extra)
    snapshot = m.tree_where(ok, state.params, state.snapshot)
    return state._replace(
        snapshot=snapshot,
        snapshot_step=jnp.where(ok, state.step, state.snapshot_step),
        tau_extra=tau_extra)


def round_contribution(cfg: OppSyncConfig, state: TrainState,
                       arrived: jnp.ndarray) -> Tuple[Any, jnp.ndarray]:
    """This pod's aggregation payload and validity under the chosen scheme.

    Dispatches through the scheme registry — ``pod_contribution`` is the
    per-pod twin of ``Scheme.aggregate``, so a newly registered scheme is
    picked up here without edits."""
    have_snap = state.snapshot_step >= 0
    return get_scheme(cfg.scheme).pod_contribution(
        state.params, state.snapshot, have_snap, arrived,
        alpha=cfg.async_alpha, a=cfg.async_a)


def round_sync(cfg: OppSyncConfig, state: TrainState,
               arrived: jnp.ndarray) -> TrainState:
    """Round-boundary aggregation across the pod axis (inside shard_map)."""
    contrib, valid = round_contribution(cfg, state, arrived)
    num = jax.lax.psum(valid, cfg.axis)
    summed = jax.tree_util.tree_map(
        lambda x: jax.lax.psum(x * valid, cfg.axis), contrib)
    # divide by the TRUE positive sum: async validity weights are fractional
    # (α(s+1)^(−a) ≈ 0.283), so an all-delayed round has 0 < Σvalid < 1 and
    # clamping the denominator to 1 would silently shrink the aggregate
    # toward zero.  num > 0 still guards the empty round.
    denom = jnp.where(num > 0, num, 1.0)
    new_params = jax.tree_util.tree_map(
        lambda s, p: jnp.where(num > 0, s / denom, p),
        summed, state.params)
    return state._replace(
        params=new_params,
        snapshot=new_params,
        snapshot_step=jnp.asarray(-1, jnp.int32),
        tau_extra=jnp.asarray(cfg.tau_extra0, jnp.float32))


def channel_trace(cfg: OppSyncConfig, key: jax.Array, n_pods: int,
                  rounds: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Simulated per-pod link condition: log-normal rates around rate0 +
    Bernoulli outages, shape (rounds, inner_steps+1, n_pods).  The final slot
    of each round drives the 'arrived' draw for the round-end upload."""
    k1, k2 = jax.random.split(key)
    shape = (rounds, cfg.inner_steps + 1, n_pods)
    rates = cfg.rate0 * jnp.exp(
        0.5 * jax.random.normal(k1, shape, jnp.float32))
    outages = jax.random.uniform(k2, shape) < cfg.outage_prob
    arrived = ~outages[:, -1, :]
    return rates, outages, arrived


def make_opp_sync_round(cfg: OppSyncConfig, train_step: Callable,
                        mesh, state_spec, batch_spec) -> Callable:
    """Build a jitted one-round function under shard_map over the pod axis.

    All TrainState leaves carry a leading pod dim sharded P(axis); batches
    carry (pod, e, local_batch...).  rates/outages: (e+1, n_pods) slices.
    """
    def one_round(state, batches, rates, outages, arrived):
        # inside shard_map: leading pod dim is local (size 1) — squeeze it
        sq = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        ex = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
        st = sq(state)
        bt = sq(batches)
        rt, ot = rates[:, 0], outages[:, 0]
        arr = arrived[0]

        def inner(st, xs):
            batch, rate, outage = xs
            st, metrics = train_step(st, batch)
            st = maybe_snapshot(cfg, st, rate, outage)
            return st, metrics["loss"]

        st, losses = jax.lax.scan(
            inner, st, (bt, rt[:cfg.inner_steps], ot[:cfg.inner_steps]))
        st = round_sync(cfg, st, arr)
        return ex(st), ex(losses)

    ax = cfg.axis
    smapped = jax.shard_map(
        one_round, mesh=mesh,
        in_specs=(state_spec, batch_spec, P(None, ax), P(None, ax), P(ax)),
        out_specs=(state_spec, P(ax, None)),
        check_vma=False)
    # both callers rebind `state, losses = one_round(state, ...)`, so the
    # old sharded state is safely donated to the new one
    return jax.jit(smapped, donate_argnums=(0,))
