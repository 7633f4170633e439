"""CPU checks of the chip benchmark's own machinery: discovery by name,
the FLOP count, the peaks table, the trace reducer and the refusal to run
without a TPU.  Nothing here needs or loads a TPU."""
import json
import os
import subprocess
import sys

import pytest

import _paths  # noqa: F401
from _paths import CHIP, ROOT
from benchlib import flops, harness, tracing
from benchlib.peaks import peaks_for

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_file_has_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells_are_found_by_name(w):
    cfg = harness.load_config(w["config"])
    assert cfg["name"] == w["config"]
    traffic = harness.load_traffic(w["traffic"])
    drv = harness.engine(traffic["engine"])
    for fn in ("setup", "window", "end_to_end", "free", "check"):
        assert callable(getattr(drv, fn))
    assert (CHIP / "limits" / f"{w['name']}.json").exists()
    per_layer = harness.metrics_of(BENCH, "per_layer", w["name"])
    assert per_layer, "every cell reports a per-layer metric"
    for m in per_layer:
        assert callable(harness.layer_reader(m["name"]))


def test_config_files_hold_only_known_keys():
    from dataclasses import fields

    from repro.core.hsfl import HSFLConfig

    own = {"name", "source", "reduced", "assumed", "fixed",
           "matmul_precision"}
    names = {f.name for f in fields(HSFLConfig)}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(cfg) - own <= names, set(cfg) - own - names
        assert set(c["reduced"]) <= set(cfg)


def test_every_metric_is_named_in_its_layer_files():
    for m in BENCH["per_layer"]:
        assert (CHIP / "layers" / f"{m['name']}.py").exists()
        for w in m.get("workloads", ()):
            assert w in {x["name"] for x in BENCH["workloads"]}


def test_cnn_flops_match_the_hand_count():
    per_layer = {"conv1": 112_896, "conv2": 451_584, "fc1": 200_704,
                 "fc2": 16_384, "fc3": 1_280}
    for name, want in per_layer.items():
        side = 28 if name == "conv1" else 14
        assert flops.cnn_forward_flops({name: flops.PAPER_CNN[name]},
                                       side) == want
    assert flops.cnn_forward_flops(flops.PAPER_CNN) == 782_848
    assert flops.cnn_train_flops(flops.PAPER_CNN) == 3 * 782_848


def test_cnn_flops_from_the_reference_shapes():
    import jax

    from benchlib.reference import cnn

    shapes = {k: v["w"].shape
              for k, v in jax.eval_shape(lambda: cnn.init(0)).items()}
    assert flops.cnn_forward_flops(shapes) == 782_848


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99")


# a small recorded trace: device ops on chip 0 and the benchmark's spans,
# in ns on the profiler clock
TRACE = tracing.Trace(
    window=(1_000, 11_000),
    spans=[("window", 1_000, 11_000), ("run_round", 1_000, 6_000),
           ("evaluate", 6_000, 7_000), ("run_round", 7_500, 10_500)],
    ops={0: [("%fusion.1 = f32[8] fusion()", 1_500, 3_000),
             ("%fusion.2 = f32[8] fusion()", 2_500, 4_000),
             ('%custom-call.3 = s8[8] custom-call(), custom_call_target='
              '"tpu_custom_call"', 5_000, 5_500),
             ("%while.4 = f32[8] while()", 6_200, 10_000),
             ("%fusion.1 = f32[8] fusion()", 8_000, 9_000),
             ("%late.5 = f32[8] fusion()", 11_500, 12_000)]})


def test_trace_union_idle_share_and_gaps():
    busy = TRACE.busy(0)
    assert busy[:2] == [(1_500, 4_000), (5_000, 5_500)]
    assert TRACE.busy_s() == pytest.approx((2_500 + 500 + 3_800) * 1e-9)
    assert TRACE.window_s() == pytest.approx(10_000e-9)
    idle = tracing.gaps(busy, 1_000, 11_000)
    assert idle == [(1_000, 1_500), (4_000, 5_000), (5_500, 6_200),
                    (10_000, 11_000)]
    by = dict(TRACE.idle_by_span())
    # gap middles 1_250, 4_500 and 5_850 lie in the first run_round span,
    # 10_500 in the second
    assert by["run_round"] == pytest.approx((500 + 1_000 + 700 + 1_000)
                                            * 1e-9)
    assert "loop" not in by
    assert tracing.innermost(TRACE.spans[1:], 7_200) == "loop"
    span_s, on_s = TRACE.busy_in("run_round")
    assert span_s == pytest.approx(8_000e-9)
    assert on_s == pytest.approx((2_500 + 500 + 2_500) * 1e-9)
    # self time: the while loop less the fusion nested in it
    assert TRACE.top_ops(3) == [("while.4", pytest.approx(2_800e-9)),
                                ("fusion.1", pytest.approx(2_500e-9)),
                                ("fusion.2", pytest.approx(1_500e-9))]


def test_run_cell_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(CHIP / "run_cell.py"),
                        "--workload", BENCH["workloads"][0]["name"],
                        "--seed", "3000000001", "--seconds", "1",
                        "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr


def test_program_seed_is_a_31_bit_function_of_the_seed():
    a = harness.program_seed(2 ** 40 + 3)
    assert a == harness.program_seed(2 ** 40 + 3)
    assert a != harness.program_seed(3)
    assert 0 <= a < 2 ** 31 - 1024
