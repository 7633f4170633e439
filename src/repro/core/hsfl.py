"""HSFL + OPT simulation driver — Algorithms 1 & 2 end to end.

Faithful reproduction of Section IV: 30 UAVs, 10 selected/round, B rounds,
e=6 local epochs, batch 10, lr 0.01, 5-layer CNN, Rician channel with
per-round K resampling, per-epoch path-loss variation (fleet movement) and
30% complete-interruption probability.  Schemes:

  'opt'      — OPT-HSFL (this paper): intermediate snapshots during local
               training rescue delayed finals (Alg. 2).
  'discard'  — HSFL with delayed updates dropped (the b=1 / dashed baseline).
  'async'    — Async-HSFL: delayed updates arrive next round and aggregate
               with the polynomial staleness weight α(s+1)^(−a) [3].

SL users train mathematically identically to FL users (SL with synchronized
FedAvg produces the same updates — the split only moves *where* layers run);
what differs is the latency/energy/payload accounting: SL transmits b·m_l +
m_a (eq. 13) and pays the BS round trip, exactly as costed in core/latency.

Two round engines share the control plane:

  fused (default) — ``core/fused_round``: channel + batches presampled
      host-side once per round, then the whole round (vmapped users, scanned
      epochs, on-device OPT scheduler, masked-mean aggregation) runs as one
      jitted device program; optional int8 delta-codec snapshots
      (``use_delta_codec``).
  host — the original Python control loop over ``OppTransmitter``; kept as
      the reference implementation.  ``tests/test_fused_round.py`` pins the
      two to identical per-round arrived/rescued/dropped trajectories.
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import latency as lat
from repro.core.channel import ChannelParams, UAVFleet
from repro.core.fused_round import build_fused_round, streams_users
from repro.core.metrics import RoundLog, SimLog
from repro.core.schemes import get_scheme
from repro.core.transmission import OppTransmitter
from repro.data.synthetic import Dataset, make_digits, make_token_shards
from repro.data.partition import partition
from repro.kernels.delta_codec.ops import codec_ratio, decode_delta, encode_delta
from repro.models import cnn as cnn_mod
from repro.models.registry import client_model
from repro.training.loss import accuracy, cross_entropy


@dataclass
class SourceKeys:
    """A language model's config.json keys, as a configuration file copies
    them from the model's source (``configs.base.SOURCE_KEYS``).  They
    record the source: each one set must equal the registry's value, and
    ``vocab_size`` is the rows of the vocabulary the cut keeps.  The CNN
    reads none of them."""
    num_hidden_layers: Optional[int] = None
    hidden_size: Optional[int] = None
    intermediate_size: Optional[int] = None
    moe_intermediate_size: Optional[int] = None
    num_attention_heads: Optional[int] = None
    num_key_value_heads: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    n_routed_experts: Optional[int] = None
    num_experts_per_tok: Optional[int] = None
    n_shared_experts: Optional[int] = None
    routed_scaling_factor: Optional[float] = None
    first_k_dense_replace: Optional[int] = None
    rms_norm_eps: Optional[float] = None
    rope_theta: Optional[float] = None
    vocab_size: Optional[int] = None
    n_group: Optional[int] = None
    topk_group: Optional[int] = None
    moe_layer_freq: Optional[int] = None
    num_nextn_predict_layers: Optional[int] = None
    max_position_embeddings: Optional[int] = None
    ep_size: Optional[int] = None


@dataclass
class HSFLConfig(SourceKeys):
    scheme: str = "opt"            # opt | discard | async
    distribution: str = "noniid"   # iid | noniid | imbalanced
    n_uavs: int = 30
    k_select: int = 10
    rounds: int = 100              # B
    local_epochs: int = 6          # e
    b: int = 2                     # transmission budget
    tau_max: float = 9.0           # seconds
    batch_size: int = 10
    lr: float = 0.01
    steps_per_epoch: int = 4       # fixed-size local epoch (single compile)
    n_train: int = 6000
    n_test: int = 1000
    cut_stage: int = 2             # SL cut: conv stages on the UE
    seed: int = 0
    # nominal payload scale: the paper's CNN transmits ~10 MB class models;
    # ours is ~1.8 MB — latency realism keeps τ_max in the paper's 8–11 s
    # regime via this override (accuracy math is unaffected).
    model_bytes: float = 10e6
    ue_model_fraction: float = 0.25
    compress_ratio: float = 1.0    # <1 when snapshots are compressed
    # int8 delta-codec snapshots (kernels/delta_codec): compress_ratio is
    # then derived from the actual int8+scale byte count of the model, and
    # rescued snapshots carry real quantization noise.  codec_block is the
    # quantization group width (lanes per absmax scale) — sweepable as a
    # group static: smaller blocks mean tighter scales (less noise) but a
    # higher wire-byte overhead (the eq. 15 frontier of arXiv:2405.00681).
    use_delta_codec: bool = False
    codec_block: int = 512
    # delta-codec bit depth: 8 (int8, ~0.252x) or 4 (int4-in-int8 storage,
    # ~0.127x wire bytes) — the sweepable rate point of the eq. 15
    # overhead-vs-delay frontier; 4-bit rescues carry ~16x the noise
    codec_bits: int = 8
    use_fused_round: bool = True   # False -> host OppTransmitter reference
    # CNN hot-path policy (kernels/fused_cnn.ForwardPolicy), device engines
    # only — the host reference loop always runs the autodiff step:
    #   kernel:      xla (custom-VJP fused step, default) | pallas | im2col
    #   precision:   f32 (value-pinned) | bf16 (mixed precision)
    #   block_k:     user-tile size of the blocked kernel grid (0 = the
    #                whole selected cohort in one grid step)
    #   batch_users: False -> legacy vmap-of-per-user-kernels step (the
    #                blocked-vs-vmapped baseline)
    kernel: str = "xla"
    precision: str = "f32"
    block_k: int = 0
    batch_users: bool = True
    schedule_override: tuple = ()  # manual opportunistic schedule (Sec. III-B)
    # UAV on-board compute range (FLOP/s).  Sec. IV doesn't specify device
    # compute; the default straddles the paper's 8-11 s tau_max sweep so the
    # participation cliff (Fig. 3d) is observable.
    flops_range: tuple = (0.8e8, 4e8)
    channel: ChannelParams = field(default_factory=ChannelParams)
    async_alpha: float = 0.4
    async_a: float = 0.5
    # the model each UAV trains: "paper-cnn" on the digits, or a language
    # model of ``repro.configs`` on non-iid token shards (each sample one
    # sequence of seq_len tokens; a client's samples index a pool of
    # token_pool sequences of its own stream; eval_seqs held-out ones).
    # The cut is the chip's share of the model (``ModelConfig.cut``):
    # layers, the routed experts held from expert_offset and the first
    # ``vocab_size`` rows of the vocabulary (0 or None keeps the model's)
    model: str = "paper-cnn"
    layers: int = 0
    experts_held: int = 0
    expert_offset: int = 0
    seq_len: int = 2048
    token_pool: int = 8
    eval_seqs: int = 8

    @property
    def token_model(self) -> bool:
        return self.model != "paper-cnn"

    def model_config(self):
        """The cut ``ModelConfig`` of a token model."""
        from repro.configs import get_config
        keys = {f.name: getattr(self, f.name) for f in fields(SourceKeys)
                if f.name != "vocab_size" and getattr(self, f.name)
                is not None}
        mcfg = get_config(self.model).check_source(keys).cut(
            layers=self.layers, experts_held=self.experts_held,
            expert_offset=self.expert_offset, vocab=self.vocab_size or 0)
        if self.seq_len > (self.max_position_embeddings or self.seq_len):
            raise ValueError(f"seq_len {self.seq_len} exceeds the model's "
                             f"{self.max_position_embeddings} positions")
        return mcfg


def init_params(cfg: HSFLConfig, key):
    """The configured model's initial weights (the trainable tree only)."""
    if cfg.token_model:
        return client_model(cfg.model_config()).init(key)
    return cnn_mod.init_cnn(key)


def model_compress_ratio(cfg: HSFLConfig) -> float:
    """The effective snapshot compression ratio for ``cfg``.

    With ``use_delta_codec`` the knob is *derived* — the actual int8+scale
    byte count of the configured model's parameter tree over its float32
    bytes (``delta_codec.ops.codec_ratio``), computed from abstract shapes
    so no params are materialized; otherwise it is the hand-set
    ``cfg.compress_ratio``.  Shared by ``HSFLSimulation`` (host/fused
    engines) and ``core/sweep`` (device engine) so the eq. 14/15 payload
    accounting cannot drift between them."""
    if not cfg.use_delta_codec:
        return cfg.compress_ratio
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(shapes))
    return codec_ratio(n, cfg.codec_block, cfg.codec_bits)


def shard_sizes(cfg: HSFLConfig) -> List[int]:
    """Samples per UAV of the digits' split without making the digits: the
    labels are the first draw of ``make_digits``'s stream."""
    n = cfg.n_train
    y = np.random.default_rng(cfg.seed).integers(0, 10, n + cfg.n_test)[:n]
    return [len(c) for c in partition(Dataset(np.arange(n), y), cfg.n_uavs,
                                      cfg.distribution, cfg.seed)]


def _heterogeneous_devices(n: int, rng: np.random.Generator,
                           flops_range=(1.5e8, 6e8)) -> List[lat.DeviceProfile]:
    return [lat.DeviceProfile(flops_per_sec=float(rng.uniform(*flops_range)))
            for _ in range(n)]


def build_sim_arrays(cfg: HSFLConfig, pad_len: int | None = None) -> Dict:
    """Per-simulation constant arrays for the on-device engine (core/sweep).

    Drawn with exactly the host simulation's seeding — data/partition from
    ``cfg.seed``, device FLOPS from ``default_rng(cfg.seed)`` in the same
    draw order as ``HSFLSimulation.__init__`` — so a device run and a host
    run with the same config see the same datasets, compute profiles and
    initial params (only the *channel/batch RNG streams* differ; see
    EXPERIMENTS.md).  Client datasets are padded to a common length
    (``pad_len`` lets a sweep pad all sims identically so they stack).
    """
    rng = np.random.default_rng(cfg.seed)
    full = make_digits(cfg.n_train + cfg.n_test, seed=cfg.seed)
    test = Dataset(full.x[cfg.n_train:], full.y[cfg.n_train:])
    train = Dataset(full.x[:cfg.n_train], full.y[:cfg.n_train])
    clients = partition(train, cfg.n_uavs, cfg.distribution, cfg.seed)
    devices = _heterogeneous_devices(cfg.n_uavs, rng, cfg.flops_range)

    m = pad_len or max(len(c) for c in clients)
    xshape = clients[0].x.shape[1:]
    client_x = np.zeros((cfg.n_uavs, m) + xshape, np.float32)
    client_y = np.zeros((cfg.n_uavs, m), clients[0].y.dtype)
    client_len = np.zeros((cfg.n_uavs,), np.int32)
    for i, c in enumerate(clients):
        k = min(len(c), m)
        client_x[i, :k] = c.x[:k]
        client_y[i, :k] = c.y[:k]
        client_len[i] = k
    return {
        "client_x": client_x,
        "client_y": client_y,
        "client_len": client_len,
        "flops": np.array([d.flops_per_sec for d in devices], np.float32),
        "samples": np.array([len(c) for c in clients], np.float32),
        "test_x": test.x.astype(np.float32),
        "test_y": test.y,
    }


def _epoch_indices(n: int, cfg: HSFLConfig, rng: np.random.Generator) -> np.ndarray:
    """Fixed-shape (steps, bs) batch indices for one local epoch."""
    need = cfg.steps_per_epoch * cfg.batch_size
    idx = rng.permutation(n)
    while len(idx) < need:
        idx = np.concatenate([idx, rng.permutation(n)])
    return idx[:need].reshape(cfg.steps_per_epoch, cfg.batch_size)


def _sample_epoch(ds: Dataset, cfg: HSFLConfig, rng: np.random.Generator):
    """Fixed-shape epoch batches (steps, bs, ...) — one jit compile total."""
    idx = _epoch_indices(len(ds), cfg, rng)
    return jnp.asarray(ds.x[idx]), jnp.asarray(ds.y[idx])


def _k_bucket(n_sched: int, k_select: int) -> int:
    """Pad K to a small even bucket so the vmapped round compiles O(1) times."""
    return min(k_select, 2 * ((n_sched + 1) // 2))


class _RoundPlan(NamedTuple):
    """A fused round's host work, done before its launch: the schedule
    and, where anyone is selected, the inputs staged on the device, the
    payloads its byte accounting reads and the ``hsfl.launch`` stats."""
    sched: list
    payload: Optional[np.ndarray] = None
    inputs: tuple = ()                 # xs, ys, chan, on the device
    launch: Optional[dict] = None


class HSFLSimulation:
    """Control plane composing jitted local training (fused or host loop)."""

    def __init__(self, cfg: HSFLConfig):
        self.cfg = cfg
        # the registered transmission policy: probe schedule, selection,
        # final deadline and aggregation all dispatch through it
        self.scheme = get_scheme(cfg.scheme)
        self.rng = np.random.default_rng(cfg.seed)
        if cfg.token_model:
            # the digits' sample counts, so the control plane is Sec. IV's
            if not cfg.use_fused_round:
                raise ValueError("token models train on the fused round "
                                 "only (use_fused_round=True)")
            self.clients, self.test = make_token_shards(
                shard_sizes(cfg), cfg.token_pool, cfg.seq_len,
                cfg.model_config().vocab_size, cfg.seed, cfg.eval_seqs)
        else:
            full = make_digits(cfg.n_train + cfg.n_test, seed=cfg.seed)
            self.test = Dataset(full.x[cfg.n_train:], full.y[cfg.n_train:])
            train = Dataset(full.x[:cfg.n_train], full.y[:cfg.n_train])
            self.clients = partition(train, cfg.n_uavs, cfg.distribution,
                                     cfg.seed)
        self.fleet = UAVFleet(cfg.n_uavs, cfg.channel, seed=cfg.seed + 1)
        self.devices = _heterogeneous_devices(cfg.n_uavs, self.rng,
                                              cfg.flops_range)
        self.workloads = [
            lat.WorkloadProfile(local_epochs=cfg.local_epochs,
                                samples=len(c)) for c in self.clients]
        # a token model's handle; its buffers (the routers' correction
        # bias) are drawn from the environment seed
        self.model = (client_model(cfg.model_config(), cfg.seed)
                      if cfg.token_model else None)
        key = jax.random.PRNGKey(cfg.seed)
        # the key is all the init reads
        init = jax.jit(self.model.init) if self.model else cnn_mod.init_cnn  # analysis: ok=jit-donate
        self.params = init(key)
        self.expert_load = None
        self._test_x = jnp.asarray(self.test.x)
        self._test_y = jnp.asarray(self.test.y)
        # Pallas kernels run in interpret mode off-TPU
        self._interpret = jax.default_backend() != "tpu"
        # the codec makes the compress knob real: actual int8+scale bytes
        # over float32 bytes for this model, not a hand-set scalar
        self.compress_ratio = model_compress_ratio(cfg)
        self._probe_epochs = self._static_schedule()
        # the next fused round's plan, prepared while this round's program
        # runs (``_run_round_fused``)
        self._next_plan: Optional[_RoundPlan] = None
        self._stack_shard = self._batch_shard = None
        self._shard_ndev = 1
        # clients stream through the round where K stacked copies of the
        # model would not fit the device
        self._streamed = cfg.use_fused_round and streams_users(
            self.params, cfg.k_select)
        devs = jax.devices()
        if cfg.use_fused_round and len(devs) > 1 and not self._streamed \
                and cfg.k_select % len(devs) == 0:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.array(devs), ("users",))
            self._stack_shard = NamedSharding(mesh, P("users"))
            self._batch_shard = NamedSharding(mesh, P(None, "users"))
            self._shard_ndev = len(devs)
        self._build_jits()

    def _static_schedule(self) -> tuple:
        """The probe schedule is static per config (Alg. 2 line 12 or the
        Sec. III-B manual override) — the scheme's decision."""
        cfg = self.cfg
        return self.scheme.static_schedule(cfg.local_epochs, cfg.b,
                                           cfg.schedule_override)

    # -- jitted kernels ----------------------------------------------------
    def _build_jits(self):
        cfg = self.cfg
        lr = cfg.lr

        def epoch_fn(params, xs, ys):
            def step(p, batch):
                bx, by = batch

                def loss(q):
                    logits = cnn_mod.forward(q, bx)
                    return cross_entropy(logits, by)

                g = jax.grad(loss)(p)
                p = jax.tree_util.tree_map(lambda w, gg: w - lr * gg, p, g)
                return p, ()

            params, _ = jax.lax.scan(step, params, (xs, ys))
            return params

        def eval_fn(params, x, y):
            logits = cnn_mod.forward(params, x)
            return cross_entropy(logits, y), accuracy(logits, y)

        # host path: all selected users advance one epoch at once (K, ...);
        # params are re-read host-side between epochs, so no donation
        self._epoch_all = jax.jit(jax.vmap(epoch_fn))  # analysis: ok=jit-donate
        self._eval = jax.jit(eval_fn)  # analysis: ok=jit-donate
        from repro.kernels.fused_cnn.ops import ForwardPolicy
        if cfg.token_model:
            self._build_token_jits()
            return
        self._fused = build_fused_round(
            **self._round_kwargs(),
            forward=ForwardPolicy(kernel=cfg.kernel,
                                  precision=cfg.precision,
                                  block_k=cfg.block_k,
                                  batch_users=cfg.batch_users).validate(),
            stacked_sharding=self._stack_shard, streamed=self._streamed)

    def _round_kwargs(self):
        cfg = self.cfg
        return dict(scheme=self.scheme, local_epochs=cfg.local_epochs,
                    steps_per_epoch=cfg.steps_per_epoch, lr=cfg.lr,
                    tau_max=cfg.tau_max, probe_epochs=self._probe_epochs,
                    async_weight=cfg.async_alpha * 2.0 ** (-cfg.async_a),
                    use_codec=cfg.use_delta_codec,
                    interpret=self._interpret, k_carry=cfg.k_select,
                    codec_block=cfg.codec_block, codec_bits=cfg.codec_bits)

    def _build_token_jits(self):
        """Round and eval of a token model: its own training step (bf16
        products against f32 master weights, f32 SGD) and held-out loss;
        stacked, the step is autodiff of its logits."""
        model = self.model
        # eval reads the weights the next round trains
        self._eval = jax.jit(model.evaluate(self.cfg.batch_size))  # analysis: ok=jit-donate
        if self._streamed:
            self._fused = build_fused_round(
                **self._round_kwargs(), streamed=True,
                client_step=model.loss_and_grad, load0=model.zero_load)
        else:
            self._fused = build_fused_round(
                **self._round_kwargs(), forward=model.logits,
                stacked_sharding=self._stack_shard)

    def evaluate(self) -> Tuple[float, float]:
        l, a = self._eval(self.params, self._test_x, self._test_y)
        return float(l), float(a)

    # -- shared per-round control plane -------------------------------------
    def _schedule_round(self):
        cfg = self.cfg
        self.fleet.resample_fading()           # per local-round K (Sec. IV)
        rates0 = self.fleet.rates()
        ue_bytes = cfg.model_bytes * cfg.ue_model_fraction
        # selection budgets the *effective* wire bytes: with the delta
        # codec on, the greedy's eq. 9-13 latency/energy (incl. the final
        # upload) must see the compressed payload — byte parity with the
        # device engine's eff_model_bytes (it used to budget the
        # uncompressed model and under-select)
        sched = self.scheme.selection_policy_host(
            rates0, self.devices, self.workloads,
            cfg.model_bytes * self.compress_ratio,
            ue_bytes * self.compress_ratio, cfg.b, cfg.tau_max, cfg.k_select)
        return sched, ue_bytes

    def run_round(self, t: int, carry_delayed) -> Tuple[RoundLog, object]:
        if self.cfg.use_fused_round:
            with TraceAnnotation("hsfl.round", round=t):
                return self._run_round_fused(t, carry_delayed)
        return self._run_round_host(t, carry_delayed)

    # -- fused engine --------------------------------------------------------
    def _presample_round(self, sched, K: int):
        """Draw the whole round's channel + batches host-side, consuming the
        fleet/simulation RNG streams in exactly the host-loop order (one
        equivalence contract, tested)."""
        cfg = self.cfg
        e, steps, bs = cfg.local_epochs, cfg.steps_per_epoch, cfg.batch_size
        n_s = len(sched)
        sel = np.array([u.index for u in sched])
        xshape = self.clients[0].x.shape[1:]
        xs = np.zeros((e, K, steps, bs) + xshape, self.clients[0].x.dtype)
        ys = np.zeros((e, K, steps, bs) + self.clients[0].y.shape[1:],
                      self.clients[0].y.dtype)
        rates = np.zeros((e, K), np.float32)
        outs = np.zeros((e, K), bool)
        for e_i in range(e):
            self.fleet.move()                  # path loss varies per epoch
            r = self.fleet.rates()
            o = self.fleet.outages()
            rates[e_i, :n_s] = r[sel]
            outs[e_i, :n_s] = o[sel]
            for j, u in enumerate(sched):
                ds = self.clients[u.index]
                idx = _epoch_indices(len(ds), cfg, self.rng)
                xs[e_i, j] = ds.x[idx]
                ys[e_i, j] = ds.y[idx]
        fr = self.fleet.rates()                # final upload: no extra move
        fo = self.fleet.outages()
        final_rate = np.zeros(K, np.float32)
        final_out = np.zeros(K, bool)
        final_rate[:n_s] = fr[sel]
        final_out[:n_s] = fo[sel]
        return xs, ys, rates, outs, final_rate, final_out

    def _user_consts(self, sched, ue_bytes: float, K: int):
        cfg = self.cfg
        n_s = len(sched)
        payload = np.full(K, cfg.model_bytes, np.float64)
        train_time = np.full(K, 1e9, np.float64)
        for j, u in enumerate(sched):
            payload[j] = cfg.model_bytes if u.mode == "FL" else ue_bytes
            train_time[j] = (
                lat.train_time_fl(self.devices[u.index], self.workloads[u.index])
                if u.mode == "FL" else
                lat.train_time_sl(self.devices[u.index], self.workloads[u.index]))
        payload *= self.compress_ratio
        rate0 = np.array([u.rate0_bps for u in sched] + [1.0] * (K - n_s))
        tau_extra0 = (cfg.b - 1) * payload * 8.0 / np.maximum(rate0, 1e-9)
        valid = np.arange(K) < n_s
        return payload, tau_extra0, train_time, valid

    def _empty_carry(self):
        # built fresh every time: the fused round *donates* the straggler
        # carry buffers, so a cached zero stack would be consumed by its
        # first use
        k = self.cfg.k_select
        stack = jax.tree_util.tree_map(
            lambda a: jax.device_put(np.zeros((k,) + a.shape, a.dtype)),
            self.params)
        return (stack, jax.device_put(np.zeros((k,), bool)))

    def _prepare_round(self) -> _RoundPlan:
        """Schedule a round and, where anyone is selected, presample it and
        stage its inputs on the device, under the spans ``hsfl.schedule``
        and ``hsfl.presample``."""
        cfg = self.cfg
        with TraceAnnotation("hsfl.schedule"):
            sched, ue_bytes = self._schedule_round()
        if not sched:
            return _RoundPlan(sched)
        # the streamed round serves every user count with one program
        K = (cfg.k_select if self._streamed
             else _k_bucket(len(sched), cfg.k_select))
        if self._shard_ndev > 1:
            # sharded user axis must stay divisible by the device count
            # (k_select is — the __init__ guard — so this stays ≤ k_select)
            K = -(-K // self._shard_ndev) * self._shard_ndev
        with TraceAnnotation("hsfl.presample"):
            xs, ys, rates, outs, final_rate, final_out = \
                self._presample_round(sched, K)
            payload, tau_extra0, train_time, valid = \
                self._user_consts(sched, ue_bytes, K)
            # dtype conversions happen host-side; a single explicit
            # device_put per input stages the round, so the loop runs clean
            # under jax.transfer_guard_host_to_device("disallow")
            chan = {
                "rates": np.asarray(rates), "outages": np.asarray(outs),
                "payload_bits": np.asarray(payload * 8.0, np.float32),
                "tau_extra0": np.asarray(tau_extra0, np.float32),
                "final_rate": np.asarray(final_rate),
                "final_outage": np.asarray(final_out),
                "train_time": np.asarray(train_time, np.float32),
                "valid": np.asarray(valid),
            }
            staged = xs.nbytes + ys.nbytes + sum(a.nbytes
                                                 for a in chan.values())
            inputs = (jax.device_put(xs, self._batch_shard),
                      jax.device_put(ys, self._batch_shard),
                      jax.device_put(chan))
        launch = dict(users=len(sched), K=K, staged_bytes=staged)
        if cfg.token_model:
            steps = len(sched) * cfg.local_epochs * cfg.steps_per_epoch
            launch.update(steps=steps,
                          tokens=steps * cfg.batch_size * cfg.seq_len)
        return _RoundPlan(sched, payload, inputs, launch)

    def _run_round_fused(self, t: int, carry_delayed):
        """One round on the fused engine.  Its host stages run under the
        profiler spans ``hsfl.schedule``, ``hsfl.presample``, ``hsfl.launch``
        and ``hsfl.readback`` (inert unless a profiler trace is running), so
        a trace can lay the device's idle gaps over them; a round with
        nobody selected stops after scheduling.  A token model's launch
        also counts its SGD ``steps`` and ``tokens``, and the streamed
        round's expert load follows the readback as ``hsfl.experts``.

        Once this round's program is dispatched, the next round is
        scheduled, presampled and staged while the device runs it (its
        ``hsfl.schedule`` and ``hsfl.presample`` nest in this round's
        ``hsfl.round``, before ``hsfl.readback``); the next call launches
        that plan, and its ``hsfl.launch`` says so with the stat ``ahead``
        = 1.  Neither stage reads a round's results, and nothing else
        draws from the fleet or ``self.rng`` between them, so every round's
        inputs are those of the serial order.  A round that dispatched
        nothing prepares nothing ahead, and the first round prepares itself.
        The last round of ``run`` thus prepares one round that never runs:
        one presample more per simulation, which changes no result."""
        cfg = self.cfg
        plan, self._next_plan = self._next_plan, None
        ahead = plan is not None
        if not ahead:
            plan = self._prepare_round()
        sched = plan.sched
        log = RoundLog(round=t, selected=len(sched))
        if isinstance(carry_delayed, (list, tuple)) and not carry_delayed:
            carry_delayed = None

        if not sched:
            # nothing selected: stragglers (async) still merge on the server
            if self.scheme.carries_delayed and carry_delayed is not None:
                stack, mask = carry_delayed
                delayed = [(jax.tree_util.tree_map(lambda a: a[i], stack), 1)
                           for i in range(mask.shape[0]) if bool(mask[i])]
                self.params = self.scheme.aggregate_host(
                    [], delayed, self.params, cfg.async_alpha, cfg.async_a)
            return log, None

        load = None
        with TraceAnnotation("hsfl.launch", **plan.launch, ahead=int(ahead)):
            xs, ys, chan = plan.inputs
            if self._streamed:
                self.params, stats, load = self._fused(self.params, xs, ys,
                                                       chan)
                new_carry = None
            elif self.scheme.carries_delayed:
                stack, mask = (carry_delayed if carry_delayed is not None
                               else self._empty_carry())
                self.params, c_stack, c_mask, stats = self._fused(
                    self.params, stack, mask, xs, ys, chan)
                new_carry = (c_stack, c_mask)
            else:
                self.params, stats = self._fused(self.params, xs, ys, chan)
                new_carry = None

        # the next round's host work, while the device runs this round
        self._next_plan = self._prepare_round()

        # the host waits here for the round program
        with TraceAnnotation("hsfl.readback"):
            arrived = np.asarray(stats.arrived)
            rescued = np.asarray(stats.rescued)
            delayed = np.asarray(stats.delayed)
            dropped = np.asarray(stats.dropped)
            sends = np.asarray(stats.opp_sends)
            log.arrived_final = int(arrived.sum())
            log.used_snapshot = int(rescued.sum())
            log.delayed = int(delayed.sum())
            log.dropped = int(dropped.sum())
            events = sends + arrived.astype(np.int64)
            log.bytes_sent = float(np.sum(plan.payload * events))
            for j, u in enumerate(sched):
                if u.mode == "SL" and events[j] > 0:
                    # one-off activation payload m_a rides the SL uplink (eq. 12)
                    wl = self.workloads[u.index]
                    log.bytes_sent += wl.act_bytes_per_sample * wl.samples
            if load:
                load = jax.device_get(load)
        if load:
            self._expert_span(load)
        return log, new_carry

    def _expert_span(self, load) -> None:
        """The round's routed-expert load, written as the stats of the
        ``hsfl.experts`` span: the most and the mean (token, slot) pairs a
        held expert of a layer took over the round, and all pairs routed to
        held experts.  ``expert_load`` keeps them and ``held``, the pairs
        per user slot, local epoch, MoE layer and held expert."""
        held = np.asarray(load["held"], np.int64)
        per_expert = held.sum(axis=(0, 1))
        stats = {"held_max": int(per_expert.max()),
                 "held_mean": float(per_expert.mean()),
                 "routed": int(held.sum())}
        self.expert_load = dict(stats, held=held)
        with TraceAnnotation("hsfl.experts", **stats):
            pass

    # -- host reference engine ----------------------------------------------
    def _run_round_host(self, t: int, carry_delayed) -> Tuple[RoundLog, List[tuple]]:
        cfg = self.cfg
        carry_delayed = list(carry_delayed or [])
        sched, ue_bytes = self._schedule_round()

        log = RoundLog(round=t, selected=len(sched))
        if not sched:
            self.params = self.scheme.aggregate_host(
                [], carry_delayed, self.params, cfg.async_alpha, cfg.async_a)
            return log, []
        txs: Dict[int, OppTransmitter] = {}
        for u in sched:
            payload = cfg.model_bytes if u.mode == "FL" else ue_bytes
            txs[u.index] = OppTransmitter(
                payload, cfg.local_epochs, cfg.b, u.rate0_bps,
                compress_ratio=self.compress_ratio,
                schedule_override=cfg.schedule_override)

        # stacked per-user params (K, ...): everyone starts from the global.
        # Pad K to a small bucket so the vmapped epoch compiles O(1) times.
        K = _k_bucket(len(sched), cfg.k_select)
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (K,) + a.shape), self.params)

        def user_tree(i: int):
            return jax.tree_util.tree_map(lambda a: a[i], stacked)

        def snapshot_of(i: int):
            if not cfg.use_delta_codec:
                return user_tree(i)
            # quantize-dequantize round trip: the server only ever holds the
            # int8 delta payload, so the stored snapshot carries codec noise
            payload = encode_delta(user_tree(i), self.params,
                                   interpret=self._interpret,
                                   block=cfg.codec_block,
                                   bits=cfg.codec_bits)
            return decode_delta(payload, self.params,
                                interpret=self._interpret)

        # local training: epochs advance in lockstep; channel drifts per epoch
        for e_t in range(1, cfg.local_epochs + 1):
            self.fleet.move()                  # path loss varies per epoch
            rates = self.fleet.rates()
            outages = self.fleet.outages()
            eb = [_sample_epoch(self.clients[u.index], cfg, self.rng)
                  for u in sched]
            while len(eb) < K:                 # pad unused slots (ignored)
                eb.append(eb[0])
            xs = jnp.stack([b[0] for b in eb])
            ys = jnp.stack([b[1] for b in eb])
            stacked = self._epoch_all(stacked, xs, ys)
            if self._probe_epochs:
                for i, u in enumerate(sched):
                    if e_t in txs[u.index].schedule:
                        txs[u.index].maybe_transmit(
                            e_t, float(rates[u.index]),
                            bool(outages[u.index]),
                            lambda i=i: snapshot_of(i))

        # final uploads
        arrived: List[object] = []
        new_delayed: List[tuple] = []
        rates = self.fleet.rates()
        outages = self.fleet.outages()
        for i, u in enumerate(sched):
            tx = txs[u.index]
            dev, wl = self.devices[u.index], self.workloads[u.index]
            tr_time = (lat.train_time_fl(dev, wl) if u.mode == "FL"
                       else lat.train_time_sl(dev, wl))
            # the scheme's deadline: extra seconds charged against τ_max
            # (0 for the paper schemes; eq. 14 allowance for 'deadline',
            # −inf — the server waits — for 'sync')
            slack = float(self.scheme.final_slack(tx.tau_extra0))
            ok = tx.final_upload(float(rates[u.index]), bool(outages[u.index]),
                                 tr_time + slack, cfg.tau_max)
            if ok:
                arrived.append(user_tree(i))
                log.arrived_final += 1
            elif self.scheme.uses_probes and tx.snapshot is not None:
                arrived.append(tx.snapshot)     # the paper's rescue
                log.used_snapshot += 1
            elif self.scheme.carries_delayed:
                new_delayed.append((user_tree(i), 1))      # max delay 1
                log.delayed += 1
            else:
                log.dropped += 1
            log.bytes_sent += tx.bytes_sent
            if u.mode == "SL" and tx.events:
                # one-off activation payload m_a rides the SL uplink (eq. 12)
                log.bytes_sent += self.workloads[u.index].act_bytes_per_sample \
                    * self.workloads[u.index].samples

        self.params = self.scheme.aggregate_host(
            arrived, carry_delayed, self.params,
            cfg.async_alpha, cfg.async_a)
        return log, new_delayed

    # -- full simulation -----------------------------------------------------
    def run(self, eval_every: int = 1, verbose: bool = False) -> SimLog:
        sim = SimLog()
        delayed: object = []
        for t in range(1, self.cfg.rounds + 1):
            log, delayed = self.run_round(t, delayed)
            if t % eval_every == 0 or t == self.cfg.rounds:
                log.test_loss, log.test_acc = self.evaluate()
            sim.add(log)
            if verbose and (t % 10 == 0 or t == 1):
                print(f"[{self.cfg.scheme}/{self.cfg.distribution} b={self.cfg.b}] "
                      f"round {t}: acc={log.test_acc:.4f} loss={log.test_loss:.4f} "
                      f"rescued={log.used_snapshot} dropped={log.dropped}")
        return sim


def run_hsfl(cfg: HSFLConfig, verbose: bool = False) -> SimLog:
    """Deprecated entry point — use ``repro.api.Experiment`` instead::

        Experiment(cfg).run(engine="fused")   # or engine="loop" with
                                              # cfg.use_fused_round=False

    Kept as a thin shim (seeded-equivalent: the facade constructs the same
    ``HSFLSimulation``)."""
    import warnings
    warnings.warn("run_hsfl is deprecated; use repro.api.Experiment(cfg)"
                  ".run(engine='fused'|'loop')", DeprecationWarning,
                  stacklevel=2)
    return HSFLSimulation(cfg).run(verbose=verbose)
