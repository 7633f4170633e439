"""CPU checks of the language-model cell's own pieces: the readers of the
``steps`` stat and the ``hsfl.experts`` span on hand-built traces (and
nothing without them), the FLOP count against a hand count at a tiny
size, the engine's refusal of a program that cannot build the model, and
a tiny run of the cell's set-up, window and check, sound and with a
planted fault, the capacity fault itself and the count of the pairs the
program's held experts did not take.  Nothing here needs or loads a
TPU."""
from dataclasses import dataclass
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
from benchlib import harness, lm_flops, lm_plants, lm_trace, tracing
from benchlib import program_trace as pt

WINDOW = (0, 100_000)
SPANS = [
    ("hsfl.round", 1_000, 40_000, {"round": 4}),
    ("hsfl.launch", 2_000, 3_000, {"users": 5, "K": 10,
                                   "staged_bytes": 10 ** 6, "steps": 30,
                                   "tokens": 30 * 4096}),
    ("hsfl.experts", 39_000, 39_100, {"held_max": 900, "held_mean": 600.0,
                                      "routed": 19_200}),
    ("hsfl.round", 50_000, 90_000, {"round": 5}),
    ("hsfl.launch", 51_000, 52_000, {"users": 2, "K": 10,
                                     "staged_bytes": 10 ** 6, "steps": 12,
                                     "tokens": 12 * 4096}),
    ("hsfl.experts", 89_000, 89_100, {"held_max": 500, "held_mean": 400.0,
                                      "routed": 12_800}),
    # outside the window
    ("hsfl.launch", 120_000, 121_000, {"users": 9, "K": 10,
                                       "staged_bytes": 1, "steps": 54}),
]
# CNN launches carry no steps stat
CNN = [sp if sp[0] != "hsfl.launch" else
       sp[:3] + ({k: v for k, v in sp[3].items()
                  if k not in ("steps", "tokens")},)
       for sp in SPANS if sp[0] != "hsfl.experts"]


def test_steps_and_the_experts_span():
    assert lm_trace.steps_in(SPANS, WINDOW) == 42
    assert lm_trace.step_device_ms(SPANS, 0.21, WINDOW) == pytest.approx(5.0)
    assert lm_trace.held_expert_load(SPANS, WINDOW) == pytest.approx(
        1400 / 1000)


def test_nothing_without_the_stats_or_spans():
    for spans in ([], CNN):
        assert lm_trace.steps_in(spans, WINDOW) is None
        assert lm_trace.step_device_ms(spans, 0.21, WINDOW) is None
        assert lm_trace.held_expert_load(spans, WINDOW) is None
    assert lm_trace.step_device_ms(SPANS, 0.0, WINDOW) is None


def _run():
    trace = tracing.Trace(
        window=WINDOW, spans=[("window", *WINDOW), ("run_round", 500, 41_000),
                              ("run_round", 49_500, 91_000)],
        ops={0: [("fusion.1", 5_000, 26_000), ("fusion.2", 60_000, 81_000)]})
    return SimpleNamespace(trace=trace, result={}, peaks=None, chips=1,
                           compiles_in_window=0)


@pytest.mark.parametrize("spans,want", [
    (SPANS, {"lm_step_device_ms": 42_000 * 1e-6 / 42,
             "held_expert_load": 1.4}),
    (CNN, {"lm_step_device_ms": None, "held_expert_load": None}),
    ([], {"lm_step_device_ms": None, "held_expert_load": None})])
def test_layer_readers(monkeypatch, spans, want):
    monkeypatch.setattr(pt, "load", lambda trace_dir=None: spans)
    for name, value in want.items():
        got = harness.layer_reader(name)(_run())
        assert got == (pytest.approx(value) if value else None), name


TINY = {"hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 8, "num_experts_per_tok": 3,
        "n_shared_experts": 2, "vocab_size": 256, "layers": 3,
        "first_k_dense_replace": 1, "experts_held": 4, "expert_offset": 2}


def test_flops_match_the_hand_count():
    T, S = 2 * 16, 16
    # per layer: q 64x96, kv_a 64x40, kv_b 32x128, o 64x64
    proj = 64 * 96 + 64 * 40 + 32 * 128 + 64 * 64
    attn = 2 * T * proj + 2 * (2 * 16 * 17 // 2) * 4 * (16 + 8 + 16)
    dense = 2 * T * 3 * 64 * 128
    moe = 2 * T * 64 * 8 + 2 * T * 3 * 64 * 64
    head = 2 * T * 64 * 256
    want = 3 * attn + dense + 2 * moe + head
    assert lm_flops.forward_flops(TINY, 2, S) == want
    assert lm_flops.expert_forward_flops(TINY, 10) == 2 * 10 * 3 * 64 * 32
    assert lm_flops.train_flops(TINY, 5, 2, S, 10) == \
        3 * (5 * want + 2 * 10 * 3 * 64 * 32)


def test_engine_refuses_a_program_without_the_model(monkeypatch):
    from repro.core import hsfl

    @dataclass
    class Old:
        scheme: str = "opt"

    monkeypatch.setattr(hsfl, "HSFLConfig", Old)
    drv = harness.engine("fused_round_lm")
    ctx = SimpleNamespace(config=harness.load_config("moonlight-16b-a3b-ep8"))
    with pytest.raises(RuntimeError, match="cannot build model"):
        drv.setup(ctx)


def _register_tiny(monkeypatch):
    """The registry's model at TINY's widths, named "moonlight-tiny"."""
    import repro.configs as configs
    from repro.configs.base import SOURCE_KEYS

    real = configs.get_config
    tiny = real("moonlight-16b-a3b").replace(
        name="moonlight-tiny", vocab_size=TINY["vocab_size"],
        **{SOURCE_KEYS[k]: v for k, v in TINY.items()
           if isinstance(SOURCE_KEYS.get(k), str)})
    monkeypatch.setattr(configs, "get_config",
                        lambda a: tiny if a == "moonlight-tiny" else real(a))
    return tiny


def _tiny_ctx(seed):
    from benchlib.spans import Spans

    cfg = harness.load_config("moonlight-16b-a3b-ep8")
    cfg.update(TINY, model="moonlight-tiny", seq_len=32, token_pool=2,
               eval_seqs=2)
    return SimpleNamespace(config=cfg,
                           traffic=harness.load_traffic("lm_round"),
                           seed=harness.program_seed(seed), spans=Spans(),
                           devices=None, chips=1, workload="tiny")


@pytest.mark.parametrize("plant", [None, "capacity"])
def test_tiny_cell_is_checked_against_the_reference(monkeypatch, plant):
    """The cell's engine at a tiny size, streamed as the memory rule puts
    a model that does not fit stacked: sound, the control plane and the
    weights follow the reference; with tokens dropped, it fails."""
    import contextlib

    from repro.core import fused_round

    monkeypatch.setattr(fused_round, "device_memory_bytes",
                        lambda device=None: 1)
    _register_tiny(monkeypatch)
    drv = harness.engine("fused_round_lm")
    ctx = _tiny_ctx(3_000_000_041)
    with (lm_plants.PLANTS[plant](ctx.config) if plant
          else contextlib.nullcontext()):
        st = drv.setup(ctx, warm=False)
        res = drv.window(st, 0.2)
    drv.free(st)
    got = drv.check(st, "tiny")
    assert res["rounds"] >= 1 and res["train_flops"] > 0
    assert got["count_rounds_off"] == 0
    if plant is None:
        # bf16 products flip a few near ties of the routing, of the about
        # 15,000 pairs the held experts take in the three rounds
        assert got["dropped_tokens"] < 100
        assert got["update1_gap"] < 0.01 and got["loss_gap"] < 0.005
    else:
        # pairs past the capacity are missing; the weights move apart too
        assert got["dropped_tokens"] > 1000 and got["update1_gap"] > 0.02


def test_capacity_fault_moves_pairs_off_the_held_experts(monkeypatch):
    """Under the capacity fault each held expert takes at most its mean
    share of a step's pairs, and the rest of its pairs reach none."""
    import jax
    import jax.numpy as jnp

    from repro.models import moe

    tiny = _register_tiny(monkeypatch).cut(layers=3, experts_held=4,
                                           expert_offset=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64))
    p = moe.init_routed_moe(jax.random.PRNGKey(1), tiny)
    bias = jnp.zeros(8).at[2].set(1.0)      # expert 2 takes every token
    _, held = jax.jit(lambda p: moe.routed_moe(p, bias, tiny, x))(p)
    with lm_plants.capacity({}):
        _, capped = jax.jit(lambda p: moe.routed_moe(p, bias, tiny, x))(p)
    cap = 64 * 3 // 8
    assert int(held[0]) == 64 and int(capped[0]) == cap
    assert (capped <= held).all() and (capped <= cap).all()


def test_dropped_pairs_is_the_shortfall_over_trained_steps():
    import numpy as np

    drv = harness.engine("fused_round_lm")
    trained = np.array([[True, True], [True, False]])
    want = np.full((2, 2, 1, 2), 10)
    got = np.full((3, 2, 1, 2), 10)               # K = 3 slots, 2 users
    got[1, 1] = 0                                 # a step no one compares
    assert drv.dropped_pairs([got], [(want, trained)]) == 0
    got[0, 0, 0, 1] = 4                           # 6 pairs missing
    assert drv.dropped_pairs([got], [(want, trained)]) == 6
    got[1, 0, 0, 0] = 13                          # 3 more elsewhere
    assert drv.dropped_pairs([got], [(want, trained)]) == 3
    # a round the reference trained no step of, and one with nobody
    none = (np.zeros((2, 2, 1, 2)), np.zeros((2, 2), bool))
    empty = (np.zeros((0, 2, 1, 2)), np.zeros((0, 2), bool))
    assert drv.dropped_pairs([got, got, None],
                             [(want, trained), none, empty]) == 3
