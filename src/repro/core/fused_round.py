"""Fused HSFL communication round — Algorithms 1 & 2 as one device program.

The host-loop reference (``HSFLSimulation._run_round_host``) pays hundreds of
dispatch round-trips per simulated round: per-epoch batch conversion, per-user
``user_tree(i)`` slicing, per-user Python ``OppTransmitter`` probes and an
O(K) aggregation loop.  This module compiles the whole round into a single
jitted function:

  - the K selected users live on a leading stacked axis (one ``vmap``);
  - the e local epochs run as ``lax.scan`` segments inside one jit, with the
    per-user SGD step lowered through the ``kernels/fused_cnn`` forward
    policy — by default the pool-first fused step with a hand-written VJP
    and a closed-form softmax-CE cotangent (bit-identical forward to
    ``cnn.forward_im2col`` at f32; ``kernel="pallas"`` routes the same
    algorithm through the Pallas kernel suite, ``precision="bf16"`` runs
    bf16 compute against f32 master params, ``kernel="im2col"`` restores
    the PR-1 autodiff step);
  - the OPT scheduler (eqs. 14–16: scheduled probes, outage voids, snapshot
    overwrite, τ_extra bookkeeping) runs on-device and branch-free through
    ``opportunistic_sync.snapshot_decision`` — the same algorithmic core the
    multi-pod OppSync feature uses, so Alg. 2 has one implementation;
  - the round ends with a single masked weighted-mean aggregation over the
    K axis (no per-user tree_map loop);
  - every scheme-specific decision (probe schedule, final deadline,
    aggregation) dispatches through a registered ``schemes.Scheme`` object
    — the engine bodies hold no per-scheme string branches, so registered
    schemes (incl. beyond-paper ones like ``deadline``) compile here
    unchanged;
  - with ``use_codec`` the snapshot state is the int8 delta-codec payload
    (kernels/delta_codec): probes quantize params−base through the Pallas
    kernel and rescues dequantize at aggregation, so the rescued
    contribution carries real quantization noise and the eq. 15 payload
    uses the actual int8+scale byte count.

Two round builders share these pieces:

- ``build_fused_round`` — inputs presampled host-side once per round
  (``hsfl._presample_round``): batch tensors of shape (e, K, steps, bs, ...)
  and per-epoch rate/outage tensors, one host→device transfer per round
  instead of e·K.  The probe *schedule* (Alg. 2 line 12 / the manual
  override of Sec. III-B) is static per configuration, so probes are
  compiled only at scheduled epoch boundaries; everything data-dependent
  (outages, τ budget, arrival, rescue, staleness) stays branch-free
  on-device.  This path replays the host numpy RNG streams bit-for-bit
  (the fused-vs-host equivalence contract).  Its streamed form
  (``streamed=True``, picked by ``streams_users`` where K stacked copies
  of the model would not fit the device) takes the same inputs and trains
  one user at a time, folding each into a running sum.
- ``build_device_round`` — the whole control plane on-device: channel/
  mobility from a ``channel_lib.FleetState`` carry, greedy selection via
  ``selection.select_users_jax``, batches gathered in-program, epochs
  scanned, eval in-program.  This is the round the sweep engine
  (``core/sweep``) chains with ``lax.scan`` and vmaps over seeds/configs.
  ``use_codec`` gives it the same int8 snapshot path: the codec state
  (int8 blocks + scales) rides the epoch scan carry, and the derived
  ``compress_ratio`` feeds selection, τ budgeting and byte metrics.
"""
from __future__ import annotations

import os
from dataclasses import replace as _dc_replace
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.channel_lib import (ChannelParams, FleetState,
                                    fleet_move, fleet_outage_step,
                                    fleet_rates, fleet_resample_fading)
from repro.core.opportunistic_sync import snapshot_decision
from repro.core.schemes import (get_scheme, kx as _kx,
                                masked_mean as _masked_mean,  # noqa: F401
                                probe_schedule_mask,
                                tree_where_k as _tree_where_k)
from repro.kernels.delta_codec.kernel import (BLOCK, dequantize_blocks,
                                              quantize_blocks)
from repro.kernels.delta_codec.ops import stacked_flatten, stacked_unflatten
from repro.kernels.fused_cnn.ops import (ForwardPolicy, make_eval_forward,
                                         make_stacked_epoch_fn,
                                         resolve_train_step)
from repro.training.loss import accuracy, cross_entropy

__all__ = ["RoundStats", "DeviceSimCarry", "DeviceRoundMetrics",
           "build_fused_round", "build_device_round", "probe_schedule_mask"]


class RoundStats(NamedTuple):
    """Per-user round outcome, device-resident until the host reads it."""
    arrived: jnp.ndarray     # (K,) bool — final upload made it (Alg. 2 l. 14)
    rescued: jnp.ndarray     # (K,) bool — snapshot substituted (the rescue)
    delayed: jnp.ndarray     # (K,) bool — carried to next round (async)
    dropped: jnp.ndarray     # (K,) bool — contributed nothing
    opp_sends: jnp.ndarray   # (K,) int32 — opportunistic transmissions sent


def _codec_encode(stacked, params, interpret: bool, block: int = BLOCK,
                  bits: int = 8):
    """Quantize the stacked users' delta vs the round-start global params
    into the int8 codec state ``(q (K, M, block), scales (K, M, 1))``."""
    delta = jax.tree_util.tree_map(lambda s, p: s - p[None], stacked, params)
    flat, _ = stacked_flatten(delta, block=block)
    k, rows, blk = flat.shape
    q, s = quantize_blocks(flat.reshape(k * rows, blk), interpret=interpret,
                           bits=bits)
    return q.reshape(k, rows, blk), s.reshape(k, rows, 1)


def _codec_decode(q, s, stacked_like, params, interpret: bool):
    """Dequantize the codec state back to a stacked params pytree — the
    rescued contribution carries true int8 quantization noise."""
    k, rows, blk = q.shape
    flat = dequantize_blocks(q.reshape(k * rows, blk),
                             s.reshape(k * rows, 1), interpret=interpret)
    delta = stacked_unflatten(flat.reshape(k, rows, blk), stacked_like)
    return jax.tree_util.tree_map(lambda d, p: p[None] + d, delta, params)


def _codec_zero_state(stacked, block: int = BLOCK):
    """All-zero codec state shaped for ``stacked`` (decodes to the global
    params; never aggregated before a probe succeeds — ``has_snap`` gates)."""
    flat, _ = stacked_flatten(stacked, block=block)
    return (jnp.zeros(flat.shape, jnp.int8),
            jnp.zeros(flat.shape[:2] + (1,), jnp.float32))


def _resolve_epoch_fns(forward: Any, lr: float, interpret: bool
                       ) -> Tuple[Callable, Callable]:
    """``(epoch_all, eval_fwd)`` for the round builders.

    Policy forwards (``ForwardPolicy`` or ``None`` → default xla/f32) get
    the *stacked-cohort* epoch (``ops.make_stacked_epoch_fn``): the K-user
    axis lives inside the blocked kernels — one batched ``dot_general``
    (xla) or one ``block_k``-tiled kernel launch (pallas) per layer per
    step — instead of ``jax.vmap`` rewriting each tiny per-user kernel
    into K grid programs.  Legacy bare callables (tests pushing non-CNN
    models through the round) keep the vmapped per-user epoch."""
    if forward is None or isinstance(forward, ForwardPolicy):
        policy = forward if forward is not None else ForwardPolicy()
        policy = _dc_replace(policy,
                             interpret=policy.interpret or interpret)
        policy.validate()
        return (make_stacked_epoch_fn(policy, lr),
                make_eval_forward(policy))
    loss_grad, fwd_eval = resolve_train_step(forward, interpret)
    return jax.vmap(_make_epoch_fn(loss_grad, lr)), fwd_eval


def _make_epoch_fn(loss_grad: Callable, lr: float) -> Callable:
    """One local epoch for one user: scan of SGD steps (Alg. 1 l. 8).

    ``loss_grad`` is the policy-resolved fused training step
    (``kernels/fused_cnn.make_loss_grad``): under the default policy the
    hand-written backward (plus the closed-form softmax-CE cotangent)
    replaces autodiff, and under ``precision="bf16"`` it computes in bf16
    internally while keeping the loss and the returned grads f32 — so the
    master params this scan carries and the SGD update stay f32 regardless
    of the compute precision."""
    def epoch_fn(params, xs, ys):
        def step(p, batch):
            bx, by = batch
            _, g = loss_grad(p, bx, by)
            p = jax.tree_util.tree_map(lambda w, gg: w - lr * gg, p, g)
            return p, ()

        params, _ = jax.lax.scan(step, params, (xs, ys))
        return params

    return epoch_fn


def device_memory_bytes(device=None) -> int:
    """Memory of ``device`` (the default device): the allocator's limit
    where the backend reports one, else the host's physical memory."""
    device = device or jax.devices()[0]
    stats = device.memory_stats() or {}
    if "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


# per user, the stacked round holds the trained weights, the snapshot and
# the gradient of one step
STACKED_COPIES = 3


def streams_users(params, k_select: int, device=None) -> bool:
    """Whether the round streams its users through one program (True) or
    stacks them over K: stacked, ``STACKED_COPIES`` copies of the parameter
    tree per user would take more than half the device's memory."""
    tree = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(params))
    return STACKED_COPIES * k_select * tree > device_memory_bytes(device) / 2


def sgd(params, grads, lr: float):
    """Plain SGD in the master weights' dtype."""
    return jax.tree_util.tree_map(lambda w, g: w - lr * g, params, grads)


def build_fused_round(*, scheme: Any, local_epochs: int, steps_per_epoch: int,
                      lr: float, tau_max: float, probe_epochs: Tuple[int, ...],
                      async_weight: float = 0.0, use_codec: bool = False,
                      interpret: bool = False, k_carry: int = 0,
                      forward: Any = None, codec_block: int = BLOCK,
                      codec_bits: int = 8,
                      stacked_sharding: Any = None, streamed: bool = False,
                      client_step: Callable | None = None,
                      load0: Any = None) -> Callable:
    """Compile one HSFL round for a fixed (scheme, e, steps, schedule).

    Returns ``round_fn(params, xs, ys, chan)`` for opt/discard, or
    ``round_fn(params, delayed_stack, delayed_mask, xs, ys, chan)`` for
    async (``delayed_stack`` leaves are (k_carry, ...)).  ``chan`` is a dict
    of device arrays: rates/outages (e, K), payload_bits/tau_extra0/
    final_rate/train_time (K,), final_outage/valid (K,) bool.  The result is
    ``(new_params, stats)`` plus ``new_delayed_stack`` for async.

    ``scheme`` is a registered ``schemes.Scheme`` (or its name): its
    ``final_slack`` shapes the arrival predicate and its ``aggregate``
    merges the round — the engine body holds no per-scheme branches beyond
    the static ``carries_delayed`` signature split.  ``forward`` is a
    ``kernels/fused_cnn.ForwardPolicy`` (or ``None`` for
    the default xla/f32 policy; a bare callable is a legacy hook used by
    tests that push non-CNN models through the round).  The round carries
    are **donated**: the caller's ``params`` (and, for async, the straggler
    ``delayed_stack``/``delayed_mask``) buffers alias the returned ones, so
    chaining rounds the way ``HSFLSimulation`` does stops copying the full
    parameter state every dispatch — do not reuse those arrays after the
    call.  ``codec_block``/``codec_bits`` are the delta-codec quantization
    group width and bit depth (``HSFLConfig.codec_block``/``codec_bits``).

    ``streamed`` builds the streamed form (``streams_users`` decides when):
    ``round_fn(params, xs, ys, chan) -> (new_params, stats, load)``, which
    trains one user at a time and folds each into a running sum, so that
    the round holds a few copies of the model whatever K is.
    ``client_step(params, x, y) -> (loss, grads, load)`` is one user's
    training step there (the model's own; by default the ``forward``
    policy's step with an empty ``load``), and ``load0`` the zero of the
    ``load`` it returns; the round sums it over each local epoch's steps
    and returns it per user slot and epoch, with leading axes (K, e).
    """
    scheme = get_scheme(scheme)
    if streamed:
        if client_step is None:
            loss_grad, _ = resolve_train_step(forward, interpret)
            client_step, load0 = (lambda p, x, y: (*loss_grad(p, x, y), {}),
                                  {})
        return _build_streamed_round(
            scheme=scheme, local_epochs=local_epochs, lr=lr,
            tau_max=tau_max, probe_epochs=probe_epochs, use_codec=use_codec,
            client_step=client_step, load0=load0)
    epoch_all, _ = _resolve_epoch_fns(forward, lr, interpret)

    if scheme.carries_delayed and k_carry < 1:
        raise ValueError(
            f"{scheme.name} build_fused_round needs k_carry >= 1 (the fixed "
            f"width of the straggler carry), got k_carry={k_carry}")

    def _train_and_probe(params, xs, ys, chan):
        k = chan["valid"].shape[0]
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (k,) + a.shape), params)
        if stacked_sharding is not None:
            # spread the user axis over host devices (bench/multi-core runs):
            # without the constraint XLA keeps the broadcast replicated and
            # every device would redo the whole K-stack of work
            stacked = jax.lax.with_sharding_constraint(stacked,
                                                       stacked_sharding)
        tau_extra = chan["tau_extra0"]
        has_snap = jnp.zeros((k,), bool)
        nsent = jnp.zeros((k,), jnp.int32)
        snap = (_codec_zero_state(stacked, codec_block) if use_codec
                else stacked)

        # epochs advance in lockstep; the probe schedule is static, so the
        # OPT transmission logic is only compiled at scheduled boundaries.
        # The named scopes (local_epoch, probe, aggregate) label the
        # compiled ops' op_name metadata and change no computation
        for e_t in range(1, local_epochs + 1):
            with jax.named_scope("local_epoch"):
                stacked = epoch_all(stacked, xs[e_t - 1], ys[e_t - 1])
            if e_t in probe_epochs:
                with jax.named_scope("probe"):
                    rate = chan["rates"][e_t - 1]
                    outage = chan["outages"][e_t - 1]
                    # eq. 15
                    tau = chan["payload_bits"] / jnp.maximum(rate, 1e-9)
                    ok, tau_extra = snapshot_decision(chan["valid"], outage,
                                                      tau, tau_extra)
                    if use_codec:
                        q_new, s_new = _codec_encode(stacked, params,
                                                     interpret, codec_block,
                                                     codec_bits)
                        snap = (jnp.where(_kx(ok, q_new), q_new, snap[0]),
                                jnp.where(_kx(ok, s_new), s_new, snap[1]))
                    else:
                        snap = _tree_where_k(ok, stacked, snap)
                    has_snap = has_snap | ok
                    nsent = nsent + ok.astype(jnp.int32)
        return stacked, snap, has_snap, nsent

    def _round_sync(params, stacked, snap, has_snap, arrived):
        """Scheme aggregation: masked mean over finals (+ rescues)."""
        if scheme.uses_probes and use_codec:
            snap = _codec_decode(snap[0], snap[1], stacked, params,
                                 interpret)
        return scheme.aggregate(params, stacked, snap, has_snap, arrived)

    if not scheme.carries_delayed:

        def round_fn(params, xs, ys, chan):
            stacked, snap, has_snap, nsent = _train_and_probe(
                params, xs, ys, chan)
            with jax.named_scope("aggregate"):
                arrived = _final_arrival(scheme, chan, tau_max)
                new_params, rescued = _round_sync(params, stacked, snap,
                                                  has_snap, arrived)
                delayed = scheme.delayed_out(chan["valid"], arrived)
                dropped = chan["valid"] & ~arrived & ~rescued & ~delayed
            return new_params, RoundStats(arrived, rescued, delayed,
                                          dropped, nsent)

        # params -> new_params aliases in place: the round loop stops
        # copying the global model every dispatch
        return jax.jit(round_fn, donate_argnums=(0,))

    # -- staleness-carrying schemes (async): the straggler stack/mask ride
    #    the round signature and the scheme's aggregate merges them --------
    aw = float(async_weight)

    def round_fn(params, delayed_stack, delayed_mask, xs, ys, chan):
        k = chan["valid"].shape[0]
        if k > k_carry:
            raise ValueError(
                f"{scheme.name} round got K={k} stacked users but the "
                f"straggler carry is only k_carry={k_carry} wide; "
                f"build_fused_round needs k_carry >= the padded user bucket "
                f"K (pass k_carry=k_select as HSFLSimulation does)")
        stacked, _, _, nsent = _train_and_probe(params, xs, ys, chan)
        with jax.named_scope("aggregate"):
            arrived = _final_arrival(scheme, chan, tau_max)
            delayed_new = scheme.delayed_out(chan["valid"], arrived)
            new_params, rescued = scheme.aggregate(
                params, stacked, None, None, arrived, delayed=delayed_stack,
                delayed_mask=delayed_mask, async_weight=aw, k_carry=k_carry)

            # next-round carry, padded to the fixed k_carry width
            pad = k_carry - k
            carry_stack = jax.tree_util.tree_map(
                lambda s: jnp.pad(s, ((0, pad),) + ((0, 0),) * (s.ndim - 1)),
                stacked)
            carry_mask = jnp.pad(delayed_new, (0, pad))
            dropped = chan["valid"] & ~arrived & ~rescued & ~delayed_new
        return (new_params, carry_stack, carry_mask,
                RoundStats(arrived, rescued, delayed_new, dropped, nsent))

    # params + the (k_carry, ...) straggler stack/mask alias their outputs:
    # the async chain stops copying the full per-user parameter stack
    # every round
    return jax.jit(round_fn, donate_argnums=(0, 1, 2))


def _final_arrival(scheme, chan, tau_max: float):
    """Alg. 2 l. 14: the final upload arrives if the link is up and
    training, the scheme's slack and the upload fit tau_max.  It reads
    only the presampled inputs, so it is known before training."""
    tau_f = chan["payload_bits"] / jnp.maximum(chan["final_rate"], 1e-9)
    fits = chan["train_time"] + scheme.final_slack(chan["tau_extra0"]) \
        + tau_f <= tau_max
    return chan["valid"] & (~chan["final_outage"]) & fits


def _build_streamed_round(*, scheme, local_epochs: int, lr: float,
                          tau_max: float, probe_epochs: Tuple[int, ...],
                          use_codec: bool, client_step: Callable,
                          load0: Any) -> Callable:
    """One round that trains the selected users one after another.

    Every decision of the round reads presampled inputs only: the probes'
    outcomes (``snapshot_decision`` over all users at each scheduled
    epoch, eqs. 14-16) and the final arrival are taken before training.
    So each user's contribution is known to be its final weights (the
    final arrives), its last snapshot (the final is lost, a probe went
    out) or nothing, and the epoch it is taken at is known too.  A
    ``fori_loop`` over the selected users (the first ``sum(valid)`` slots;
    a dynamic trip count, so one compile serves every user count and no
    padded user trains) runs each user's epochs from the global weights
    and, at that epoch, folds the weights into a running sum with the
    weight the scheme's per-user ``pod_contribution`` gives them; no
    snapshot is copied.  The round ends with the sum over its weight, or
    the global weights where nothing arrived."""
    if scheme.carries_delayed or not scheme.per_user_fold:
        raise ValueError(
            f"scheme {scheme.name!r} aggregates all K users at once, which "
            f"the streamed round (one user at a time) cannot do; it streams "
            f"only schemes whose aggregate is a per-user weighted mean")
    if use_codec:
        raise ValueError("the streamed round carries no delta-codec state")

    def round_fn(params, xs, ys, chan):
        valid = chan["valid"]
        k = valid.shape[0]
        arrived = _final_arrival(scheme, chan, tau_max)
        tau_extra = chan["tau_extra0"]
        has_snap = jnp.zeros((k,), bool)
        nsent = jnp.zeros((k,), jnp.int32)
        snap_epoch = jnp.full((k,), -1, jnp.int32)
        with jax.named_scope("probe"):
            for e_t in probe_epochs:
                tau = chan["payload_bits"] / jnp.maximum(
                    chan["rates"][e_t - 1], 1e-9)                 # eq. 15
                ok, tau_extra = snapshot_decision(
                    valid, chan["outages"][e_t - 1], tau, tau_extra)
                snap_epoch = jnp.where(ok, e_t - 1, snap_epoch)
                has_snap = has_snap | ok
                nsent = nsent + ok.astype(jnp.int32)
        # the epoch after which each user's contribution is taken
        fold_epoch = jnp.where(arrived, local_epochs - 1, snap_epoch)

        def user(u, carry):
            def epoch(c, inp):
                p, acc, wsum = c
                x, y, e_i = inp

                def step(c, batch):
                    p, load = c
                    _, g, used = client_step(p, *batch)
                    return (sgd(p, g, lr), jax.tree_util.tree_map(
                        jnp.add, load, used)), ()

                with jax.named_scope("local_epoch"):
                    (p, load), _ = jax.lax.scan(step, (p, load0), (x, y))
                with jax.named_scope("aggregate"):
                    contrib, w = scheme.pod_contribution(
                        p, p, has_snap[u], arrived[u])
                    now = e_i == fold_epoch[u]
                    acc = jax.lax.cond(
                        now, lambda a: jax.tree_util.tree_map(
                            lambda s, c: s + w * c, a, contrib),
                        lambda a: a, acc)
                return (p, acc, wsum + jnp.where(now, w, 0.0)), load

            acc, wsum, loads = carry
            xu = jax.lax.dynamic_index_in_dim(xs, u, 1, keepdims=False)
            yu = jax.lax.dynamic_index_in_dim(ys, u, 1, keepdims=False)
            (_, acc, wsum), load = jax.lax.scan(
                epoch, (params, acc, wsum),
                (xu, yu, jnp.arange(local_epochs)))
            loads = jax.tree_util.tree_map(
                lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, u, 0),
                loads, load)
            return acc, wsum, loads

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        loads = jax.tree_util.tree_map(
            lambda z: jnp.zeros((k, local_epochs) + z.shape, z.dtype), load0)
        acc, wsum, load = jax.lax.fori_loop(
            0, jnp.sum(valid.astype(jnp.int32)), user,
            (zero, jnp.zeros((), jnp.float32), loads))
        with jax.named_scope("aggregate"):
            denom = jnp.where(wsum > 0, wsum, 1.0)
            new_params = jax.tree_util.tree_map(
                lambda a, p: jnp.where(wsum > 0, a / denom, p), acc, params)
            rescued = ~arrived & has_snap
            delayed = scheme.delayed_out(valid, arrived)
            dropped = valid & ~arrived & ~rescued & ~delayed
        return new_params, RoundStats(arrived, rescued, delayed, dropped,
                                      nsent), load

    return jax.jit(round_fn, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Fully on-device round: FleetState carry, channel realized in-program
# ---------------------------------------------------------------------------

class DeviceSimCarry(NamedTuple):
    """lax.scan carry for a whole simulation (core/sweep.py).

    ``delayed``/``delayed_mask`` are the async straggler carry; for
    opt/discard they ride along untouched (zeros) so every scheme scans with
    one carry structure."""
    params: Any
    fleet: FleetState
    delayed: Any             # stacked (K, ...) params pytree
    delayed_mask: jnp.ndarray   # (K,) bool


class DeviceRoundMetrics(NamedTuple):
    """Per-round scalars, device-resident until the sweep finishes."""
    selected: jnp.ndarray    # int32 — users scheduled this round
    arrived: jnp.ndarray     # int32 — finals that made it (Alg. 2 l. 14)
    rescued: jnp.ndarray     # int32 — snapshot substitutions
    delayed: jnp.ndarray     # int32 — carried to next round (async)
    dropped: jnp.ndarray     # int32 — contributed nothing
    bytes_sent: jnp.ndarray  # float32 — uplink bytes this round
    test_loss: jnp.ndarray   # float32
    test_acc: jnp.ndarray    # float32


def build_device_round(*, scheme: Any, local_epochs: int,
                       steps_per_epoch: int, batch_size: int, lr: float,
                       k_select: int, channel: ChannelParams,
                       model_bytes: float, ue_model_fraction: float,
                       compress_ratio: float = 1.0,
                       use_codec: bool = False, interpret: bool = False,
                       speed_mps: float = 15.0, epoch_seconds: float = 1.0,
                       schedule_override: Tuple[int, ...] = (),
                       async_alpha: float = 0.4, async_a: float = 0.5,
                       max_sl: int | None = None,
                       act_bytes_per_sample: float = 3136.0,
                       codec_block: int = BLOCK, codec_bits: int = 8,
                       forward: Any = None) -> Callable:
    """One HSFL round with the *entire* control plane on-device.

    Unlike ``build_fused_round`` (which consumes host-presampled channel
    tensors so it can replay the numpy reference stream bit-for-bit), this
    round takes a ``channel_lib.FleetState`` in its carry and realizes fleet
    movement, Rician rates and the Gilbert–Elliott outage chain in-program,
    selects users with ``select_users_jax``, and gathers training batches
    from the stacked client datasets by on-device indices — so whole
    simulations chain under ``lax.scan`` and whole sweeps under ``vmap``
    (core/sweep.py) with zero host round trips.

    ``use_codec`` (probing schemes) stores snapshots as the int8/int4
    delta-codec state (``kernels/delta_codec``): scheduled probes quantize
    params − round-start-global through the Pallas kernel into a
    ``(K, M, BLOCK)`` int8 + per-block-scale carry that rides the epoch
    ``lax.scan``, and rescues dequantize at aggregation, so a rescued
    contribution carries true quantization noise.  ``compress_ratio``
    (derive it from ``delta_codec.ops.codec_ratio`` when the codec is on)
    scales the eq. 15 ``payload_bits`` — and, through them, the
    ``select_users_jax`` latency/energy accounting, the eq. 14 τ_extra
    budget, the final-arrival τ and the wire-byte metrics.  Everything is
    a ``where`` over the traced ``b``/``tau_max``/``bandwidth_ratio``
    config axes, so codec grids vmap/shard exactly like uncompressed ones.

    Returns ``round_fn(carry, round_key, sim, cfg) -> (carry, metrics)``:

    - ``carry``: ``DeviceSimCarry`` (global params, fleet, async stragglers);
    - ``round_key``: per-round PRNG key (batch index stream);
    - ``sim``: per-simulation constants — ``client_x`` (N, M, ...),
      ``client_y`` (N, M), ``client_len``/``flops``/``samples`` (N,),
      ``test_x``/``test_y``;
    - ``cfg``: traced scalars ``b``/``tau_max``/``bandwidth_ratio`` — the
      vmappable config axes of a sweep.

    RNG streams (fleet state + batch indices) are jax.random, not the host
    numpy generators: device runs are seeded and self-consistent but not
    bit-identical to the host reference (see EXPERIMENTS.md).

    ``forward`` is a ``kernels/fused_cnn.ForwardPolicy`` (``None`` → the
    default xla/f32 policy): local training runs through its custom-VJP
    training step, in-program eval through its (value-identical) plain
    forward.  ``codec_block`` is the quantization group width of the
    delta-codec snapshot carry.  The returned ``round_fn`` is *unjitted* —
    the sweep engine scans it and donates the whole ``DeviceSimCarry``
    (params, fleet, stragglers) at its own jit boundary.
    """
    epoch_all, fwd_eval = _resolve_epoch_fns(forward, lr, interpret)
    scheme = get_scheme(scheme)
    aw = float(async_alpha) * 2.0 ** (-float(async_a))
    # the codec (or a manual compress_ratio) shrinks every model payload on
    # the wire, so the *effective* bytes drive selection feasibility/energy
    # (eqs. 9–13), the eq. 14/15 τ budgets and the byte metrics alike
    eff_model_bytes = model_bytes * compress_ratio
    eff_ue_bytes = eff_model_bytes * ue_model_fraction
    use_codec = bool(use_codec) and scheme.supports_codec
    K = k_select
    p = channel

    def round_fn(carry: DeviceSimCarry, rkey, sim: Dict[str, Any],
                 cfg: Dict[str, Any]):
        params, fleet = carry.params, carry.fleet
        b, tau_max = cfg["b"], cfg["tau_max"]
        bw = cfg.get("bandwidth_ratio", 1.0)

        # -- schedule (Alg. 1 l. 3-5): fresh fading, greedy selection -------
        fleet = fleet_resample_fading(fleet, p)
        rates0 = fleet_rates(fleet, p, bw)
        sel, mode_sl, valid, n_taken, tt_fl, tt_sl = scheme.selection_policy(
            rates0, sim["flops"], sim["samples"], b=b, tau_max=tau_max,
            k_select=K, model_bytes=eff_model_bytes,
            ue_model_bytes=eff_ue_bytes,
            local_epochs=local_epochs, max_sl=max_sl,
            act_bytes_per_sample=act_bytes_per_sample)
        train_time = jnp.where(mode_sl, tt_sl[sel], tt_fl[sel])
        train_time = jnp.where(valid, train_time, 1e9)
        payload_bits = jnp.where(mode_sl, eff_ue_bytes, eff_model_bytes) \
            * 8.0                                              # eq. (15) m_i
        tau_extra0 = jnp.maximum(b - 1.0, 0.0) * payload_bits \
            / jnp.maximum(rates0[sel], 1e-9)                   # eq. (14)

        # -- local training: epochs in lockstep, channel drifts per epoch.
        # Epochs run as a lax.scan (one compiled epoch body — measurably
        # faster than the unrolled python loop on CPU and ~e× smaller to
        # compile, which matters when a sweep compiles 3 scheme programs).
        # Probes therefore run masked every epoch via probe_schedule_mask
        # (the schedule depends on the *traced* budget b anyway).
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (K,) + a.shape), params)
        clen = jnp.maximum(sim["client_len"][sel], 1)
        xshape = sim["client_x"].shape[2:]
        override = (jnp.asarray(schedule_override, jnp.int32)
                    if schedule_override else None)

        def epoch_body(carry_e, e_t):
            fleet, stacked, snap, has_snap, nsent, tau_extra = carry_e
            fleet = fleet_move(fleet, p, speed_mps, epoch_seconds)
            rate_e = fleet_rates(fleet, p, bw)[sel]
            fleet, bad = fleet_outage_step(fleet, p)
            out_e = bad[sel]
            idx = jax.random.randint(
                jax.random.fold_in(rkey, e_t),
                (K, steps_per_epoch * batch_size), 0, clen[:, None])
            # one fused (user, sample) gather — never materializes the
            # (K, M, ...) per-round client slice under a config vmap
            xs = sim["client_x"][sel[:, None], idx].reshape(
                (K, steps_per_epoch, batch_size) + xshape)
            ys = sim["client_y"][sel[:, None], idx].reshape(
                (K, steps_per_epoch, batch_size))
            stacked = epoch_all(stacked, xs, ys)
            if scheme.uses_probes:
                sched = scheme.probe_schedule(e_t, local_epochs, b,
                                              override=override)
                tau = payload_bits / jnp.maximum(rate_e, 1e-9)   # eq. (15)
                ok, tau_extra = snapshot_decision(valid & sched, out_e,
                                                  tau, tau_extra)
                if use_codec:
                    # the snapshot carry is the int8 payload itself, so the
                    # epoch scan carries ~4x fewer snapshot bytes and the
                    # rescue later decodes with true quantization noise
                    q_new, s_new = _codec_encode(stacked, params, interpret,
                                                 codec_block, codec_bits)
                    snap = (jnp.where(_kx(ok, q_new), q_new, snap[0]),
                            jnp.where(_kx(ok, s_new), s_new, snap[1]))
                else:
                    snap = _tree_where_k(ok, stacked, snap)
                has_snap = has_snap | ok
                nsent = nsent + ok.astype(jnp.int32)
            return (fleet, stacked, snap, has_snap, nsent, tau_extra), ()

        snap0 = (_codec_zero_state(stacked, codec_block) if use_codec
                 else stacked)
        carry_e = (fleet, stacked, snap0, jnp.zeros((K,), bool),
                   jnp.zeros((K,), jnp.int32), tau_extra0)
        carry_e, _ = jax.lax.scan(epoch_body, carry_e,
                                  jnp.arange(1, local_epochs + 1))
        fleet, stacked, snap, has_snap, nsent, _ = carry_e

        # -- final upload (Alg. 2 l. 14): no extra move -----------------------
        rate_f = fleet_rates(fleet, p, bw)[sel]
        fleet, bad_f = fleet_outage_step(fleet, p)
        tau_f = payload_bits / jnp.maximum(rate_f, 1e-9)
        fits = train_time + scheme.final_slack(tau_extra0) + tau_f <= tau_max
        arrived = valid & (~bad_f[sel]) & fits

        # -- aggregation (registry dispatch — no scheme branches) -------------
        if use_codec:
            snap = _codec_decode(snap[0], snap[1], stacked, params,
                                 interpret)
        new_params, rescued = scheme.aggregate(
            params, stacked, snap, has_snap, arrived, delayed=carry.delayed,
            delayed_mask=carry.delayed_mask, async_weight=aw, k_carry=K)
        delayed_new = scheme.delayed_out(valid, arrived)
        dropped = valid & ~arrived & ~rescued & ~delayed_new
        if scheme.carries_delayed:
            new_carry = DeviceSimCarry(new_params, fleet, stacked,
                                       delayed_new)
        else:
            new_carry = DeviceSimCarry(new_params, fleet, carry.delayed,
                                       carry.delayed_mask)

        # -- byte accounting + eval ------------------------------------------
        events = nsent + arrived.astype(jnp.int32)
        bytes_sent = jnp.sum(jnp.where(valid,
                                       payload_bits / 8.0 * events, 0.0))
        act = act_bytes_per_sample * sim["samples"][sel]
        bytes_sent = bytes_sent + jnp.sum(
            jnp.where(valid & mode_sl & (events > 0), act, 0.0))
        logits = fwd_eval(new_params, sim["test_x"])
        metrics = DeviceRoundMetrics(
            selected=n_taken,
            arrived=jnp.sum(arrived.astype(jnp.int32)),
            rescued=jnp.sum(rescued.astype(jnp.int32)),
            delayed=jnp.sum(delayed_new.astype(jnp.int32)),
            dropped=jnp.sum(dropped.astype(jnp.int32)),
            bytes_sent=bytes_sent.astype(jnp.float32),
            test_loss=cross_entropy(logits, sim["test_y"]),
            test_acc=accuracy(logits, sim["test_y"]))
        return new_carry, metrics

    return round_fn
