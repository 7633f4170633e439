"""Transmission schemes as first-class API objects — the pluggable registry.

The paper's contribution *is* a transmission scheme (opportunistic proactive
upload vs. the sync/async/discard baselines), yet until PR 5 a scheme was a
string branched inside every engine (``HSFLSimulation``, ``build_fused_round``,
``build_device_round``, ``compile_spec``).  This module makes the scheme the
unit of extension: a ``Scheme`` object owns the three decisions every engine
delegates —

  1. **probe schedule** — when Alg. 2 probes the channel for an opportunistic
     snapshot: ``static_schedule`` (host engines, compile-time epochs) and
     ``probe_schedule`` (device engines, a branch-free mask over a *traced*
     budget b);
  2. **selection policy** — which users are scheduled each round:
     ``selection_policy`` wraps ``selection.select_users_jax`` (device) and
     ``selection_policy_host`` wraps ``selection.schedule_users`` (host);
  3. **aggregation** — how the round's contributions merge into the global
     model: ``aggregate`` (stacked (K, ...) device form) and
     ``aggregate_host`` (list-of-pytrees host form);

plus the **final-upload deadline** knob ``final_slack`` (extra seconds charged
against τ_max at the end-of-round upload) and static engine facts
(``uses_probes``, ``carries_delayed``, ``supports_codec``,
``lowered_program``).  Every method an engine traces is jit-compatible, so a
registered scheme runs unchanged on all three engines: the host reference
loop, the fused single-round program and the scanned/vmapped sweep engine.

Registered paper schemes (Sec. III / Fig. 3):

  ``opt``      — OPT-HSFL: scheduled probes under the eq. 14 τ_extra budget,
                 snapshots rescue missed finals (Alg. 2).
  ``sync``     — fully synchronous HSFL: the server *waits* for every
                 scheduled final (no τ_max cutoff; only an upload-time outage
                 loses an update) — the latency-unconstrained envelope.
  ``async``    — Async-HSFL: delayed updates arrive next round, merged with
                 the polynomial staleness weight α(s+1)^(−a) [3].
  ``discard``  — delayed updates dropped (the b=1 / dashed baseline).

Beyond-paper scheme shipped through the same registry (the proof the API
composes):

  ``deadline`` — overhead-aware OPT after arXiv:2405.00681: the eq. 14 probe
                 allowance τ_extra0 = (b−1)·m_i/r_i^0 is *charged against the
                 round deadline*, so a final upload only counts if
                 t_train + τ_extra0 + τ_f ≤ τ_max.  Budgeting more probes
                 tightens the final deadline — the overhead-vs-delay frontier
                 — while snapshots still rescue what the deadline drops.

Extending: subclass ``Scheme``, decorate with ``@register_scheme("name")``,
and the scheme is immediately runnable through ``repro.api.Experiment`` on
every engine, sweepable via ``SweepSpec.schemes`` entries, and selectable
from the benchmark CLIs — no engine edits.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import fedavg, fedasync_merge, fedasync_weight
from repro.core.selection import schedule_users, select_users_jax
from repro.core.transmission import scheduled_epochs


# ---------------------------------------------------------------------------
# stacked-axis aggregation primitives (shared by every scheme + both device
# engines; formerly private to fused_round)
# ---------------------------------------------------------------------------

def kx(flags: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a (K,) flag vector against a (K, ...) leaf."""
    return flags.reshape(flags.shape + (1,) * (leaf.ndim - 1))


def tree_where_k(flags, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(kx(flags, x), x, y), a, b)


def masked_mean(contrib, weights, fallback):
    """Σ_i w_i·x_i / Σ_i w_i over the K axis; ``fallback`` when Σ w = 0.

    The denominator is the *true* positive sum — clamping it to 1 (the old
    ``jnp.maximum(num, 1.0)``) silently shrinks the mean whenever the
    weights are fractional and sum below 1 (the async staleness weights
    α(s+1)^(−a) ≈ 0.283 do exactly that; same bug class as the fixed
    ``opportunistic_sync.round_sync``)."""
    num = jnp.sum(weights)
    denom = jnp.where(num > 0, num, 1.0)
    return jax.tree_util.tree_map(
        lambda c, p: jnp.where(
            num > 0, jnp.sum(c * kx(weights, c), axis=0) / denom, p),
        contrib, fallback)


def _rank_pos(weights, leaf_ndim: int):
    """Sort-rank helpers shared by the masked robust aggregates: the valid
    count m, and the rank index j broadcast against a sorted (K, ...) leaf.
    Invalid slots are pushed to +inf before the sort, so ranks [0, m) are
    exactly the valid values in ascending order."""
    m = jnp.sum(weights)
    K = weights.shape[0]
    j = jnp.arange(K, dtype=jnp.float32).reshape(
        (K,) + (1,) * (leaf_ndim - 1))
    return m, j


def trimmed_mean(contrib, weights, fallback, trim: float = 0.25):
    """Coordinate-wise α-trimmed mean over the K axis (Byzantine-robust).

    Each coordinate sorts its m = Σw valid entries (invalid slots ride to
    +inf past them), drops g = ⌊trim·m⌋ from each tail — clipped so at
    least one rank survives — and averages ranks [g, m−g).  Selection is
    ``jnp.where`` on position weights, *never* a multiply: the +inf
    padding times a zero weight would be NaN.  Backend-agnostic: the same
    function serves the stacked device engines and (stacked by
    ``_host_stack``) the host server, which is what makes the
    host-vs-fused pins bit-comparable."""
    def one(c, p):
        m, j = _rank_pos(weights, c.ndim)
        s = jnp.sort(jnp.where(kx(weights, c) > 0, c, jnp.inf), axis=0)
        g = jnp.maximum(jnp.minimum(jnp.floor(trim * m),
                                    jnp.floor((m - 1.0) / 2.0)), 0.0)
        keep = (j >= g) & (j < m - g)
        cnt = jnp.maximum(m - 2.0 * g, 1.0)
        val = jnp.sum(jnp.where(keep, s, 0.0), axis=0) / cnt
        return jnp.where(m > 0, val, p)
    return jax.tree_util.tree_map(one, contrib, fallback)


def masked_median(contrib, weights, fallback):
    """Coordinate-wise median over the m = Σw valid slots of the K axis
    (even m averages the two middle ranks).  Rank selection is a one-hot
    ``jnp.where`` sum — branch-free under a traced m."""
    def one(c, p):
        m, j = _rank_pos(weights, c.ndim)
        s = jnp.sort(jnp.where(kx(weights, c) > 0, c, jnp.inf), axis=0)
        lo = jnp.floor((m - 1.0) / 2.0)
        hi = jnp.ceil((m - 1.0) / 2.0)
        med = 0.5 * (jnp.sum(jnp.where(j == lo, s, 0.0), axis=0)
                     + jnp.sum(jnp.where(j == hi, s, 0.0), axis=0))
        return jnp.where(m > 0, med, p)
    return jax.tree_util.tree_map(one, contrib, fallback)


def clipped_mean(contrib, weights, fallback):
    """Masked mean of norm-clipped updates: each slot's delta from the
    global model is scaled down to the masked *median* of the valid delta
    norms (the adaptive clip radius — no tuning knob), then masked-mean.
    A single exploded upload can move the mean by at most the typical
    honest update norm."""
    sq = jax.tree_util.tree_map(
        lambda c, p: jnp.sum((c - p) ** 2,
                             axis=tuple(range(1, c.ndim))),
        contrib, fallback)
    norms = jnp.sqrt(sum(jax.tree_util.tree_leaves(sq)))       # (K,)
    m, j = _rank_pos(weights, 1)
    s = jnp.sort(jnp.where(weights > 0, norms, jnp.inf))
    lo = jnp.floor((m - 1.0) / 2.0)
    hi = jnp.ceil((m - 1.0) / 2.0)
    med = 0.5 * (jnp.sum(jnp.where(j == lo, s, 0.0))
                 + jnp.sum(jnp.where(j == hi, s, 0.0)))
    scale = jnp.minimum(1.0, med / jnp.maximum(norms, 1e-12))  # (K,)
    clipped = jax.tree_util.tree_map(
        lambda c, p: p + kx(scale, c) * (c - p), contrib, fallback)
    return masked_mean(clipped, weights, fallback)


def _host_stack(arrived):
    """List-of-pytrees -> (stacked (n, ...) tree, all-ones weights): the
    adapter that lets ``aggregate_host`` reuse the exact stacked-axis
    robust aggregate the device engines trace."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *arrived)
    return stacked, jnp.ones((len(arrived),), jnp.float32)


def async_merge(params, stacked, delayed_stack, delayed_mask, arrived,
                aw: float, k_carry: int):
    """Async aggregation: timely finals at weight 1, prior-round stragglers
    at α(s+1)^(−a); a round with only stragglers falls back to the
    sequential FedAsync server merge (never a full replace)."""
    w_t = arrived.astype(jnp.float32)                      # (K,)
    w_d = delayed_mask.astype(jnp.float32) * aw            # (k_carry,)
    n_arr = jnp.sum(w_t)
    total = n_arr + jnp.sum(w_d)
    mixed = jax.tree_util.tree_map(
        lambda s, d, p: jnp.where(
            total > 0,
            (jnp.sum(s * kx(w_t, s), axis=0)
             + jnp.sum(d * kx(w_d, d), axis=0))
            / jnp.maximum(total, 1e-9), p),
        stacked, delayed_stack, params)

    seq = params
    for i in range(k_carry):          # static unroll; k_carry is small
        seq = jax.tree_util.tree_map(
            lambda acc, d: jnp.where(delayed_mask[i],
                                     (1.0 - aw) * acc + aw * d[i], acc),
            seq, delayed_stack)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(n_arr > 0, a, b), mixed, seq)


def probe_schedule_mask(e_t: int, local_epochs: int, b) -> jnp.ndarray:
    """``transmission.scheduled_epochs`` membership with a *traced* budget.

    The host schedule is {k·period : 1 ≤ k ≤ b−1, k·period < e} with
    period = max(1, round(e/b)); that set is exactly the e_t with
    e_t ≡ 0 (mod period), e_t < e and e_t ≤ (b−1)·period, which this
    evaluates branch-free so ``b`` can live on a vmapped config axis.
    ``tests/test_sweep.py`` pins the two over an (e, b) grid.
    """
    bf = jnp.asarray(b, jnp.float32)
    period = jnp.clip(jnp.round(local_epochs / jnp.maximum(bf, 1.0)),
                      1.0, float(local_epochs))
    et = jnp.asarray(e_t, jnp.float32)
    return ((jnp.mod(et, period) == 0) & (et < local_epochs)
            & (et <= (bf - 1.0) * period))


def probe(scheduled, outage, rate, payload_bits, tau_extra
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One opportunistic probe (Alg. 2 l. 12–21), branch-free and
    shape-polymorphic: a scheduled probe succeeds iff the channel is up and
    the upload delay τ at ``rate`` (eq. 15) fits the remaining allowance;
    success burns τ from the budget (eq. 16).  Returns (ok, new_tau_extra).
    The one probe of every engine: per pod under shard_map
    (``opportunistic_sync``) and over the (K,) user axis of the stacked,
    streamed and device rounds (``fused_round``)."""
    tau = payload_bits / jnp.maximum(rate, 1e-9)             # eq. (15)
    ok = scheduled & (~outage) & (tau <= tau_extra)
    return ok, jnp.where(ok, tau_extra - tau, tau_extra)


# ---------------------------------------------------------------------------
# the Scheme protocol
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Scheme:
    """One transmission policy, decomposed into the decisions every engine
    makes.  Instances are frozen/hashable (they ride static jit arguments
    and program-cache keys); ``pins`` carries per-scheme sweep pins — the
    ``("opt", {"b": 2.0})`` dict of the legacy ``SweepSpec`` entry form —
    onto the object itself (``with_pins``).

    The base class implements the *discard/sync family*: no probes, no
    straggler carry, FedAvg over whatever arrived.  Subclasses override
    only the decisions that differ.
    """
    pins: Tuple[Tuple[str, Any], ...] = ()

    # -- static engine facts (class attributes, NOT dataclass fields: the
    #    registry stamps ``name`` onto the class, and identity/equality is
    #    (class, pins)) --------------------------------------------------
    name = "base"
    uses_probes = False        # compile the Alg. 2 probe/snapshot block
    carries_delayed = False    # the async straggler carry is live
    supports_codec = False     # snapshots exist -> codec state is meaningful
    # the aggregate is a weighted mean of per-user contributions, which
    # ``pod_contribution`` gives one user at a time (the streamed round)
    per_user_fold = True

    def with_pins(self, **pins) -> "Scheme":
        """A copy with sweep pins (b/τ_max/group statics) attached."""
        merged = dict(self.pins)
        merged.update(pins)
        return replace(self, pins=tuple(sorted(merged.items())))

    # -- decision 1: probe schedule -----------------------------------------
    def static_schedule(self, local_epochs: int, b: int,
                        override: Sequence[int] = ()) -> Tuple[int, ...]:
        """Compile-time probe epochs for the host/fused engines (Alg. 2
        line 12, or the Sec. III-B manual override)."""
        return ()

    def probe_schedule(self, e_t, local_epochs: int, b,
                       override=None) -> jnp.ndarray:
        """Traced probe mask for the device engine: is local epoch ``e_t``
        a scheduled probe under (possibly traced) budget ``b``?"""
        return jnp.zeros((), bool)

    # -- decision 2: selection policy ---------------------------------------
    def selection_policy(self, rates0, flops, samples, *, b, tau_max,
                         k_select: int, model_bytes: float,
                         ue_model_bytes: float, local_epochs: int,
                         max_sl=None, **lat_kw):
        """Which users train this round (device engines): the greedy
        energy-per-sample selection of Alg. 1 l. 3-5 by default.  Returns
        ``select_users_jax``'s fixed-width slot arrays."""
        return select_users_jax(
            rates0, flops, samples, b=b, tau_max=tau_max, k_select=k_select,
            model_bytes=model_bytes, ue_model_bytes=ue_model_bytes,
            local_epochs=local_epochs, max_sl=max_sl, **lat_kw)

    def selection_policy_host(self, rates0, devices, workloads,
                              model_bytes: float, ue_model_bytes: float,
                              b: int, tau_max: float, k_select: int):
        """Host-engine twin of ``selection_policy`` (Python greedy)."""
        return schedule_users(rates0, devices, workloads, model_bytes,
                              ue_model_bytes, b, tau_max, k_select)

    # -- final-upload deadline ----------------------------------------------
    def final_slack(self, tau_extra0):
        """Extra seconds charged against τ_max at the final upload:
        ``arrived`` requires t_train + final_slack + τ_f ≤ τ_max.

        0 for the paper schemes (shape-preserving so the traced arrival
        predicate is bit-identical to the pre-registry engines); the
        ``deadline`` scheme charges the eq. 14 probe allowance, ``sync``
        returns −inf (the server waits).  Works on host floats and device
        arrays alike."""
        return tau_extra0 * 0.0

    # -- decision 3: aggregation --------------------------------------------
    def aggregate(self, params, contribs, snapshots, has_snap, arrived, *,
                  delayed=None, delayed_mask=None, async_weight: float = 0.0,
                  k_carry: int = 0):
        """Merge the round into the global model (device engines).

        ``contribs`` are the stacked (K, ...) locally-trained params,
        ``snapshots``/``has_snap`` the opportunistic snapshot state,
        ``delayed``/``delayed_mask`` the staleness carry.  Returns
        ``(new_params, rescued)`` with ``rescued`` a (K,) bool mask."""
        rescued = jnp.zeros_like(arrived)
        new = masked_mean(contribs, arrived.astype(jnp.float32), params)
        return new, rescued

    def aggregate_host(self, arrived, delayed, global_params,
                       alpha: float = 0.4, a: float = 0.5):
        """Host-engine twin of ``aggregate``: ``arrived`` is a list of
        pytrees (finals + any rescued snapshots), ``delayed`` a list of
        ``(update, staleness)`` tuples."""
        if not arrived:
            return global_params
        return fedavg(arrived)

    def pod_contribution(self, params, snapshot, have_snap, arrived, *,
                         alpha: float = 0.4, a: float = 0.5):
        """Per-pod twin of ``aggregate`` for the shard_map engine
        (``opportunistic_sync``): this pod's payload and its weight in
        the cross-pod mean.  ``arrived``/``have_snap`` are scalar bools
        local to the pod; returns ``(contrib, valid)`` with ``valid`` a
        scalar f32 weight.  Base: a missed final contributes nothing
        (discard/sync)."""
        del snapshot, have_snap, alpha, a
        return params, arrived.astype(jnp.float32)

    def delayed_out(self, valid, arrived) -> jnp.ndarray:
        """Which users enter next round's staleness carry."""
        return jnp.zeros_like(arrived)

    # -- sweep-engine program identity --------------------------------------
    def lowered_program(self, b_vals: Tuple[float, ...]) -> str:
        """The scheme whose round *program* executes a sweep group pinned to
        budget values ``b_vals`` — normally ``self.name``; a scheme may
        reroute onto another scheme's compile when the two provably
        coincide there (``discard`` @ b=1 is opt with zero probes)."""
        return self.name


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

SCHEMES: Dict[str, Scheme] = {}


def register_scheme(name: str):
    """Class decorator: instantiate and register a Scheme under ``name``.

    The registered instance is the canonical one — ``get_scheme(name)``
    returns it, ``SweepSpec``/``Experiment`` resolve strings through it,
    and the benchmark CLIs list it as a ``--scheme`` choice."""
    def deco(cls):
        if name in SCHEMES:
            raise ValueError(f"scheme {name!r} is already registered "
                             f"({SCHEMES[name].__class__.__name__})")
        taken = next((n for n, s in SCHEMES.items() if s.__class__ is cls),
                     None)
        if taken is not None:
            # stamping a second name onto the same class would retroactively
            # rename the registered singleton (name is a class attribute so
            # that frozen-dataclass replace()/with_pins preserve it)
            raise ValueError(
                f"{cls.__name__} is already registered as {taken!r}; "
                f"subclass it to register an alias")
        cls.name = name
        SCHEMES[name] = cls()
        return cls
    return deco


def registered_schemes() -> Tuple[str, ...]:
    """Sorted names of every registered scheme."""
    return tuple(sorted(SCHEMES))


def get_scheme(scheme) -> Scheme:
    """Resolve a scheme name (or pass a ``Scheme`` instance through).

    Raises a ``ValueError`` naming every registered scheme on an unknown
    string — the error the sweep compiler and every engine surface."""
    if isinstance(scheme, Scheme):
        return scheme
    try:
        return SCHEMES[scheme]
    except KeyError:
        raise ValueError(
            f"unknown transmission scheme {scheme!r}; registered schemes: "
            f"{', '.join(registered_schemes())} "
            f"(add one with @repro.core.schemes.register_scheme)") from None


# ---------------------------------------------------------------------------
# the four paper schemes
# ---------------------------------------------------------------------------

@register_scheme("discard")
class DiscardScheme(Scheme):
    """HSFL with delayed updates dropped (the b=1 / dashed baseline).
    Aggregation/probes are the base class; only the sweep lowering is its
    own: at b=1 the probe schedule is empty and the eq. 14 allowance is 0,
    so no snapshot ever exists and the rescue weights vanish identically —
    discard IS opt there, and the group shares opt's compile."""

    def lowered_program(self, b_vals: Tuple[float, ...]) -> str:
        return "opt" if tuple(b_vals) == (1.0,) else self.name


@register_scheme("sync")
class SyncScheme(Scheme):
    """Fully synchronous HSFL: the server waits for every scheduled final
    upload regardless of τ_max (only an upload-time outage loses one) —
    the latency-unconstrained envelope the deadline-bound schemes trade
    against."""

    def final_slack(self, tau_extra0):
        return tau_extra0 * 0.0 - math.inf     # t + (−inf) ≤ τ_max always


@register_scheme("opt")
class OptScheme(Scheme):
    """OPT-HSFL (this paper): scheduled probes under the eq. 14 τ_extra
    budget; the latest snapshot rescues a missed final (Alg. 2)."""
    uses_probes = True
    supports_codec = True

    def static_schedule(self, local_epochs: int, b: int,
                        override: Sequence[int] = ()) -> Tuple[int, ...]:
        if b <= 1:
            return ()
        sched = (tuple(override) if override
                 else tuple(scheduled_epochs(local_epochs, b)))
        return tuple(e for e in sched if 1 <= e <= local_epochs)

    def probe_schedule(self, e_t, local_epochs: int, b,
                       override=None) -> jnp.ndarray:
        if override is not None:
            return jnp.any(e_t == override)
        return probe_schedule_mask(e_t, local_epochs, b)

    def aggregate(self, params, contribs, snapshots, has_snap, arrived, *,
                  delayed=None, delayed_mask=None, async_weight: float = 0.0,
                  k_carry: int = 0):
        rescued = (~arrived) & has_snap
        contrib = tree_where_k(arrived, contribs, snapshots)
        weights = (arrived | rescued).astype(jnp.float32)
        return masked_mean(contrib, weights, params), rescued

    def pod_contribution(self, params, snapshot, have_snap, arrived, *,
                         alpha: float = 0.4, a: float = 0.5):
        del alpha, a
        contrib = jax.tree_util.tree_map(
            lambda p, s: jnp.where(arrived, p, s), params, snapshot)
        return contrib, (arrived | have_snap).astype(jnp.float32)


@register_scheme("async")
class AsyncScheme(Scheme):
    """Async-HSFL: delayed updates arrive next round and aggregate with the
    polynomial staleness weight α(s+1)^(−a) [3]."""
    carries_delayed = True
    per_user_fold = False

    def aggregate(self, params, contribs, snapshots, has_snap, arrived, *,
                  delayed=None, delayed_mask=None, async_weight: float = 0.0,
                  k_carry: int = 0):
        new = async_merge(params, contribs, delayed, delayed_mask, arrived,
                          float(async_weight), k_carry)
        return new, jnp.zeros_like(arrived)

    def aggregate_host(self, arrived, delayed, global_params,
                       alpha: float = 0.4, a: float = 0.5):
        delayed = list(delayed or [])
        if arrived:
            updates = list(arrived)
            weights = [1.0] * len(arrived)
            for upd, staleness in delayed:
                updates.append(upd)
                weights.append(fedasync_weight(staleness, alpha, a))
            return fedavg(updates, weights)
        if delayed:
            # only stragglers: the sequential FedAsync server merge
            # ω ← (1−α_t)·ω + α_t·ω_d — never a full replace
            out = global_params
            for upd, staleness in delayed:
                out = fedasync_merge(out, upd, staleness, alpha, a)
            return out
        return global_params

    def pod_contribution(self, params, snapshot, have_snap, arrived, *,
                         alpha: float = 0.4, a: float = 0.5):
        del snapshot, have_snap
        # the delayed update arrives anyway, one round stale [3]
        w = alpha * 2.0 ** (-a)
        return params, jnp.where(arrived, 1.0, w)

    def delayed_out(self, valid, arrived) -> jnp.ndarray:
        return valid & ~arrived


# ---------------------------------------------------------------------------
# beyond-paper: deadline-aware OPT (arXiv:2405.00681)
# ---------------------------------------------------------------------------

@register_scheme("deadline")
class DeadlineScheme(OptScheme):
    """Overhead-aware OPT: the eq. 14 probe allowance is charged against the
    round deadline, so a final upload only arrives if
    t_train + τ_extra0 + τ_f ≤ τ_max.  A bigger probe budget b buys more
    rescue opportunities but tightens the final deadline — the
    overhead-vs-delay frontier of arXiv:2405.00681.  Snapshots still rescue
    what the deadline drops, so the scheme degrades toward opt's rescue
    path rather than discard's drops.  At b=1 the allowance is exactly 0
    and the scheme coincides with opt (and hence discard)."""

    def final_slack(self, tau_extra0):
        return tau_extra0


# ---------------------------------------------------------------------------
# Byzantine-robust OPT variants (the lossy-wire PR): same probe/rescue
# machinery as opt, but the aggregate survives CRC-clean corruption —
# pre-encode bit flips (the ``flip`` fault) that checksums cannot see.
# Registered like any scheme: zero engine edits, automatically swept by
# the contracts/CI registry iteration.
# ---------------------------------------------------------------------------

@register_scheme("opt_trimmed")
class OptTrimmedScheme(OptScheme):
    """OPT with a coordinate-wise trimmed-mean aggregate: the ⌊trim·m⌋
    largest and smallest entries of every coordinate are dropped before
    averaging, so a minority of exploded uploads cannot move the model.
    ``aggregate_host`` stacks the arrived list and calls the *same*
    ``trimmed_mean`` the device engines trace (host-vs-fused pinned)."""
    per_user_fold = False
    trim = 0.25

    def aggregate(self, params, contribs, snapshots, has_snap, arrived, *,
                  delayed=None, delayed_mask=None, async_weight: float = 0.0,
                  k_carry: int = 0):
        rescued = (~arrived) & has_snap
        contrib = tree_where_k(arrived, contribs, snapshots)
        weights = (arrived | rescued).astype(jnp.float32)
        return trimmed_mean(contrib, weights, params, self.trim), rescued

    def aggregate_host(self, arrived, delayed, global_params,
                       alpha: float = 0.4, a: float = 0.5):
        if not arrived:
            return global_params
        stacked, w = _host_stack(arrived)
        return trimmed_mean(stacked, w, global_params, self.trim)


@register_scheme("opt_median")
class OptMedianScheme(OptScheme):
    """OPT with a coordinate-wise median aggregate — the max-breakdown
    member of the robust family (tolerates just under half the uploads
    being arbitrary)."""
    per_user_fold = False

    def aggregate(self, params, contribs, snapshots, has_snap, arrived, *,
                  delayed=None, delayed_mask=None, async_weight: float = 0.0,
                  k_carry: int = 0):
        rescued = (~arrived) & has_snap
        contrib = tree_where_k(arrived, contribs, snapshots)
        weights = (arrived | rescued).astype(jnp.float32)
        return masked_median(contrib, weights, params), rescued

    def aggregate_host(self, arrived, delayed, global_params,
                       alpha: float = 0.4, a: float = 0.5):
        if not arrived:
            return global_params
        stacked, w = _host_stack(arrived)
        return masked_median(stacked, w, global_params)


@register_scheme("opt_clip")
class OptClipScheme(OptScheme):
    """OPT with adaptive norm clipping: every update's delta is clipped
    to the median valid delta norm before the masked mean — cheap, and
    keeps honest-majority rounds near the plain mean."""
    per_user_fold = False

    def aggregate(self, params, contribs, snapshots, has_snap, arrived, *,
                  delayed=None, delayed_mask=None, async_weight: float = 0.0,
                  k_carry: int = 0):
        rescued = (~arrived) & has_snap
        contrib = tree_where_k(arrived, contribs, snapshots)
        weights = (arrived | rescued).astype(jnp.float32)
        return clipped_mean(contrib, weights, params), rescued

    def aggregate_host(self, arrived, delayed, global_params,
                       alpha: float = 0.4, a: float = 0.5):
        if not arrived:
            return global_params
        stacked, w = _host_stack(arrived)
        return clipped_mean(stacked, w, global_params)
