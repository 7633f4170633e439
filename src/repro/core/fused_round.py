"""The HSFL round (Algorithms 1 & 2) as device programs: one core, three
drivers.

The core is Alg. 2's transmission logic, written once:

- ``schemes.probe`` — a scheduled probe's eq. 15 delay and its eqs. 14–16
  snapshot decision (outage voids, τ_extra budget);
- ``snapshot_format`` — what a snapshot is: the stacked (K, ...) params, or
  with the delta codec the int8 state of params − round-start global
  (``kernels/delta_codec``), so a rescued contribution carries real
  quantization noise;
- ``final_arrival`` — whether the final upload arrives (Alg. 2 l. 14);
- ``settle`` — the round's masks after the scheme's ``aggregate``.

Every scheme-specific decision (probe schedule, final slack, aggregate,
straggler carry) dispatches through a registered ``schemes.Scheme``; no
driver branches on a scheme's name.  The drivers:

- stacked (``build_fused_round``) — inputs presampled host-side once per
  round (``hsfl._presample_round``), the K users on a leading axis, epochs
  unrolled with probes compiled only at the static scheduled epochs.  It
  replays the host numpy RNG streams bit for bit;
- streamed (``build_fused_round(streamed=True)``, picked by
  ``streams_users`` where K stacked copies of the model would not fit) —
  the same inputs, one user trained at a time and folded into a running
  sum;
- device (``build_device_round``) — the whole control plane in-program
  (fleet channel, selection, batch gathers, eval), epochs scanned with
  masked probes; the sweep engine (``core/sweep``) chains it with
  ``lax.scan`` and vmaps it over seeds and configs.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, replace as _dc_replace
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.channel_lib import (ChannelParams, FleetState,
                                    fleet_move, fleet_outage_step,
                                    fleet_rates, fleet_resample_fading)
from repro.core.schemes import (get_scheme,
                                masked_mean as _masked_mean,  # noqa: F401
                                probe, probe_schedule_mask,
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


# ---------------------------------------------------------------------------
# the core: snapshot format, final arrival, settlement
# ---------------------------------------------------------------------------

class StackedSnapshots:
    """A snapshot is the users' stacked (K, ...) params themselves."""

    def empty(self, stacked):
        return stacked

    def take(self, ok, stacked, params, snap):
        return _tree_where_k(ok, stacked, snap)

    def restore(self, snap, stacked, params):
        return snap


@dataclass(frozen=True)
class CodecSnapshots:
    """A snapshot is the int8 delta-codec state ``(q (K, M, block), scales
    (K, M, 1))`` of the users' params less the round-start global params;
    the eq. 15 payload counts its int8+scale bytes (``compress_ratio``), and
    the device round's epoch scan carries about 4x fewer snapshot bytes."""
    interpret: bool
    block: int = BLOCK
    bits: int = 8

    def empty(self, stacked):
        """All-zero state (decodes to the global params; never aggregated
        before a probe succeeds — ``has_snap`` gates)."""
        flat, _ = stacked_flatten(stacked, block=self.block)
        return (jnp.zeros(flat.shape, jnp.int8),
                jnp.zeros(flat.shape[:2] + (1,), jnp.float32))

    def take(self, ok, stacked, params, snap):
        """Quantize the users' delta through the Pallas kernel; keep it
        where ``ok``."""
        delta = jax.tree_util.tree_map(lambda s, p: s - p[None], stacked,
                                       params)
        flat, _ = stacked_flatten(delta, block=self.block)
        k, rows, blk = flat.shape
        q, s = quantize_blocks(flat.reshape(k * rows, blk),
                               interpret=self.interpret, bits=self.bits)
        return _tree_where_k(ok, (q.reshape(k, rows, blk),
                                  s.reshape(k, rows, 1)), snap)

    def restore(self, snap, stacked, params):
        """Dequantize back to a stacked params pytree."""
        q, s = snap
        k, rows, blk = q.shape
        flat = dequantize_blocks(q.reshape(k * rows, blk),
                                 s.reshape(k * rows, 1),
                                 interpret=self.interpret)
        delta = stacked_unflatten(flat.reshape(k, rows, blk), stacked)
        return jax.tree_util.tree_map(lambda d, p: p[None] + d, delta, params)


def snapshot_format(scheme, use_codec: bool, interpret: bool,
                    block: int = BLOCK, bits: int = 8):
    """The round's snapshot format: ``empty(stacked)``, ``take(ok,
    stacked, params, snap)`` and ``restore(snap, stacked, params)``.  The
    codec applies only to schemes whose snapshots exist."""
    if use_codec and scheme.supports_codec:
        return CodecSnapshots(interpret, block, bits)
    return StackedSnapshots()


def final_arrival(scheme, valid, final_outage, final_rate, payload_bits,
                  train_time, tau_extra0, tau_max):
    """Alg. 2 l. 14: the final upload arrives if the link is up and
    training, the scheme's slack and the upload fit tau_max."""
    tau_f = payload_bits / jnp.maximum(final_rate, 1e-9)
    fits = train_time + scheme.final_slack(tau_extra0) + tau_f <= tau_max
    return valid & (~final_outage) & fits


def settle(scheme, valid, arrived, rescued, opp_sends) -> RoundStats:
    """The round's outcome once the aggregate has named the rescued: who
    enters the straggler carry, and who contributed nothing."""
    delayed = scheme.delayed_out(valid, arrived)
    dropped = valid & ~arrived & ~rescued & ~delayed
    return RoundStats(arrived, rescued, delayed, dropped, opp_sends)


# ---------------------------------------------------------------------------
# local training, and whether K stacked users fit the device
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# the stacked and streamed drivers: presampled inputs
# ---------------------------------------------------------------------------

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

    Returns ``round_fn(params, xs, ys, chan)`` for schemes without a
    straggler carry, or ``round_fn(params, delayed_stack, delayed_mask, xs,
    ys, chan)`` for those with one (async; ``delayed_stack`` leaves are
    (k_carry, ...)).  ``chan`` is a dict of device arrays: rates/outages
    (e, K), payload_bits/tau_extra0/final_rate/train_time (K,),
    final_outage/valid (K,) bool.  The result is ``(new_params, stats)``,
    with the next ``delayed_stack, delayed_mask`` before ``stats`` for a
    straggler carry.

    ``scheme`` is a registered ``schemes.Scheme`` (or its name).
    ``forward`` is a ``kernels/fused_cnn.ForwardPolicy`` (or ``None`` for
    the default xla/f32 policy; a bare callable is a legacy hook used by
    tests that push non-CNN models through the round).  The round carries
    are **donated**: the caller's ``params`` (and the straggler
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
    fmt = snapshot_format(scheme, use_codec, interpret, codec_block,
                          codec_bits)

    if scheme.carries_delayed and k_carry < 1:
        raise ValueError(
            f"{scheme.name} build_fused_round needs k_carry >= 1 (the fixed "
            f"width of the straggler carry), got k_carry={k_carry}")

    def body(params, xs, ys, chan, **carry):
        valid = chan["valid"]
        k = valid.shape[0]
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
        snap = fmt.empty(stacked)

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
                    ok, tau_extra = probe(valid, outage, rate,
                                          chan["payload_bits"], tau_extra)
                    snap = fmt.take(ok, stacked, params, snap)
                    has_snap = has_snap | ok
                    nsent = nsent + ok.astype(jnp.int32)
        with jax.named_scope("aggregate"):
            arrived = final_arrival(
                scheme, valid, chan["final_outage"], chan["final_rate"],
                chan["payload_bits"], chan["train_time"], chan["tau_extra0"],
                tau_max)
            new_params, rescued = scheme.aggregate(
                params, stacked, fmt.restore(snap, stacked, params),
                has_snap, arrived, **carry)
            stats = settle(scheme, valid, arrived, rescued, nsent)
        return new_params, stacked, stats

    if not scheme.carries_delayed:

        def round_fn(params, xs, ys, chan):
            new_params, _, stats = body(params, xs, ys, chan)
            return new_params, stats

        # params -> new_params aliases in place: the round loop stops
        # copying the global model every dispatch
        return jax.jit(round_fn, donate_argnums=(0,))

    aw = float(async_weight)

    def round_fn(params, delayed_stack, delayed_mask, xs, ys, chan):
        k = chan["valid"].shape[0]
        if k > k_carry:
            raise ValueError(
                f"{scheme.name} round got K={k} stacked users but the "
                f"straggler carry is only k_carry={k_carry} wide; "
                f"build_fused_round needs k_carry >= the padded user bucket "
                f"K (pass k_carry=k_select as HSFLSimulation does)")
        new_params, stacked, stats = body(
            params, xs, ys, chan, delayed=delayed_stack,
            delayed_mask=delayed_mask, async_weight=aw, k_carry=k_carry)
        with jax.named_scope("aggregate"):
            # next-round carry, padded to the fixed k_carry width
            pad = k_carry - k
            carry_stack = jax.tree_util.tree_map(
                lambda s: jnp.pad(s, ((0, pad),) + ((0, 0),) * (s.ndim - 1)),
                stacked)
            carry_mask = jnp.pad(stats.delayed, (0, pad))
        return new_params, carry_stack, carry_mask, stats

    # params + the (k_carry, ...) straggler stack/mask alias their outputs:
    # the async chain stops copying the full per-user parameter stack
    # every round
    return jax.jit(round_fn, donate_argnums=(0, 1, 2))


def _build_streamed_round(*, scheme, local_epochs: int, lr: float,
                          tau_max: float, probe_epochs: Tuple[int, ...],
                          use_codec: bool, client_step: Callable,
                          load0: Any) -> Callable:
    """One round that trains the selected users one after another.

    Every decision of the round reads presampled inputs only: the probes'
    outcomes (``probe`` over all users at each scheduled epoch) and the
    final arrival are taken before training.  So each user's contribution
    is known to be its final weights (the final arrives), its last
    snapshot (the final is lost, a probe went out) or nothing, and the
    epoch it is taken at is known too.  A ``fori_loop`` over the selected
    users (the first ``sum(valid)`` slots; a dynamic trip count, so one
    compile serves every user count and no padded user trains) runs each
    user's epochs from the global weights and, at that epoch, folds the
    weights into a running sum with the weight the scheme's per-user
    ``pod_contribution`` gives them; no snapshot is copied.  The round ends
    with the sum over its weight, or the global weights where nothing
    arrived."""
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
        arrived = final_arrival(
            scheme, valid, chan["final_outage"], chan["final_rate"],
            chan["payload_bits"], chan["train_time"], chan["tau_extra0"],
            tau_max)
        tau_extra = chan["tau_extra0"]
        has_snap = jnp.zeros((k,), bool)
        nsent = jnp.zeros((k,), jnp.int32)
        snap_epoch = jnp.full((k,), -1, jnp.int32)
        with jax.named_scope("probe"):
            for e_t in probe_epochs:
                ok, tau_extra = probe(valid, chan["outages"][e_t - 1],
                                      chan["rates"][e_t - 1],
                                      chan["payload_bits"], tau_extra)
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
            stats = settle(scheme, valid, arrived, ~arrived & has_snap,
                           nsent)
        return new_params, stats, load

    return jax.jit(round_fn, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# the device driver: FleetState carry, channel realized in-program
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

    ``use_codec`` (probing schemes) keeps snapshots in the delta-codec
    format (``CodecSnapshots``), whose state rides the epoch ``lax.scan``.
    ``compress_ratio`` (derive it from ``delta_codec.ops.codec_ratio`` when
    the codec is on) scales the eq. 15 ``payload_bits`` — and, through
    them, the ``select_users_jax`` latency/energy accounting, the eq. 14
    τ_extra budget, the final-arrival τ and the wire-byte metrics.
    Everything is a ``where`` over the traced ``b``/``tau_max``/
    ``bandwidth_ratio`` config axes, so codec grids vmap/shard exactly like
    uncompressed ones.

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
    forward.  The returned ``round_fn`` is *unjitted* — the sweep engine
    scans it and donates the whole ``DeviceSimCarry`` (params, fleet,
    stragglers) at its own jit boundary.
    """
    epoch_all, fwd_eval = _resolve_epoch_fns(forward, lr, interpret)
    scheme = get_scheme(scheme)
    aw = float(async_alpha) * 2.0 ** (-float(async_a))
    # the codec (or a manual compress_ratio) shrinks every model payload on
    # the wire, so the *effective* bytes drive selection feasibility/energy
    # (eqs. 9–13), the eq. 14/15 τ budgets and the byte metrics alike
    eff_model_bytes = model_bytes * compress_ratio
    eff_ue_bytes = eff_model_bytes * ue_model_fraction
    fmt = snapshot_format(scheme, use_codec, interpret, codec_block,
                          codec_bits)
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
        # Epochs run as a lax.scan, one compiled epoch body in place of e,
        # which keeps each scheme program of a sweep small to compile.
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
                ok, tau_extra = probe(valid & sched, out_e, rate_e,
                                      payload_bits, tau_extra)
                snap = fmt.take(ok, stacked, params, snap)
                has_snap = has_snap | ok
                nsent = nsent + ok.astype(jnp.int32)
            return (fleet, stacked, snap, has_snap, nsent, tau_extra), ()

        carry_e = (fleet, stacked, fmt.empty(stacked), jnp.zeros((K,), bool),
                   jnp.zeros((K,), jnp.int32), tau_extra0)
        carry_e, _ = jax.lax.scan(epoch_body, carry_e,
                                  jnp.arange(1, local_epochs + 1))
        fleet, stacked, snap, has_snap, nsent, _ = carry_e

        # -- final upload (Alg. 2 l. 14): no extra move -----------------------
        rate_f = fleet_rates(fleet, p, bw)[sel]
        fleet, bad_f = fleet_outage_step(fleet, p)
        arrived = final_arrival(scheme, valid, bad_f[sel], rate_f,
                                payload_bits, train_time, tau_extra0,
                                tau_max)

        # -- aggregation (registry dispatch — no scheme branches) -------------
        new_params, rescued = scheme.aggregate(
            params, stacked, fmt.restore(snap, stacked, params), has_snap,
            arrived, delayed=carry.delayed, delayed_mask=carry.delayed_mask,
            async_weight=aw, k_carry=K)
        stats = settle(scheme, valid, arrived, rescued, nsent)
        if scheme.carries_delayed:
            new_carry = DeviceSimCarry(new_params, fleet, stacked,
                                       stats.delayed)
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
            delayed=jnp.sum(stats.delayed.astype(jnp.int32)),
            dropped=jnp.sum(stats.dropped.astype(jnp.int32)),
            bytes_sent=bytes_sent.astype(jnp.float32),
            test_loss=cross_entropy(logits, sim["test_y"]),
            test_acc=accuracy(logits, sim["test_y"]))
        return new_carry, metrics

    return round_fn
