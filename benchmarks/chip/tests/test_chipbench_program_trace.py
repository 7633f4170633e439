"""CPU checks of ``benchlib/program_trace.py``: the reductions of the
program's ``hsfl.*`` host spans against device ops on a small hand-built
trace, the readers of the six per-layer metrics over it, and the reading
of a host plane that a CPU profiler trace records."""
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
from benchlib import harness, program_trace as pt, tracing

WINDOW = (0, 100_000)


def _launch(users, K, staged):
    return {"users": users, "K": K, "staged_bytes": staged}


# two rounds inside the window and one after it, in ns
SPANS = [
    ("hsfl.round", 1_000, 41_000, {"round": 1}),
    ("hsfl.schedule", 1_000, 5_000, {}),
    ("hsfl.presample", 5_000, 20_000, {}),
    ("hsfl.launch", 20_000, 22_000, _launch(3, 4, 2_000_000)),
    ("hsfl.readback", 22_000, 40_000, {}),
    ("hsfl.round", 50_000, 90_000, {"round": 2}),
    ("hsfl.schedule", 50_000, 54_000, {}),
    ("hsfl.presample", 54_000, 70_000, {}),
    ("hsfl.launch", 70_000, 72_000, _launch(4, 4, 3_000_000)),
    ("hsfl.readback", 72_000, 89_000, {}),
    ("hsfl.round", 105_000, 115_000, {"round": 3}),
    ("hsfl.launch", 106_000, 107_000, _launch(1, 2, 9_000_000)),
]
# device-0 ops: a while loop with a fusion nested in it, a gap between two
# ops of the first round's program, eval between rounds
OPS = [
    ("%while.1 = f32[8] while()", 21_000, 30_000),
    ("%fusion.2 = f32[8] fusion()", 22_000, 28_000),
    ("%fusion.3 = f32[8] fusion()", 30_000, 31_000),
    ("%copy.5 = f32[8] copy()", 33_000, 34_000),
    ("%dot.4 = f32[8] dot()", 41_500, 43_000),
    ("%while.1 = f32[8] while()", 71_000, 80_000),
    ("%fusion.3 = f32[8] fusion()", 80_000, 84_000),
]
BUSY = tracing.union((s, e) for _, s, e in OPS)


@pytest.mark.parametrize("name,idle_ns", [
    ("hsfl.schedule", (4_000 + 4_000) / 2),
    ("hsfl.presample", (15_000 + 16_000) / 2),
    # the device starts 1_000 ns into each launch
    ("hsfl.launch", (1_000 + 1_000) / 2),
    # the 2_000 gap, and idle once the program ends at 34_000 and 84_000
    ("hsfl.readback", (2_000 + 6_000 + 5_000) / 2),
])
def test_idle_inside_each_stage_per_round(name, idle_ns):
    assert pt.stage_idle_ms(SPANS, BUSY, WINDOW, name) == \
        pytest.approx(idle_ns * 1e-6)


def test_idle_after_the_last_op_of_each_readback():
    # the program ends at 34_000 and 84_000; readbacks end at 40_000, 89_000
    assert pt.tail_idle_ms(SPANS, BUSY, WINDOW, "hsfl.readback") == \
        pytest.approx((6_000 + 5_000) / 2 * 1e-6)
    # a gap inside a span that the device closes is not a tail
    assert pt.tail_idle_ms(SPANS, BUSY, WINDOW, "hsfl.launch") == 0


def test_counters_over_the_windows_launches():
    assert pt.padded_user_share(SPANS, WINDOW) == pytest.approx(
        100.0 * (1 + 0) / (4 + 4))
    assert pt.staged_mb(SPANS, WINDOW) == pytest.approx(2.5)


def test_none_without_program_spans():
    assert pt.stage_idle_ms([], BUSY, WINDOW, "hsfl.schedule") is None
    assert pt.tail_idle_ms([], BUSY, WINDOW, "hsfl.readback") is None
    assert pt.padded_user_share([], WINDOW) is None
    assert pt.staged_mb([], WINDOW) is None
    # spans, but no round inside the window
    assert pt.stage_idle_ms(SPANS, BUSY, (200_000, 300_000),
                            "hsfl.schedule") is None


def _run():
    trace = tracing.Trace(
        window=WINDOW,
        spans=[("window", *WINDOW), ("run_round", 500, 41_500),
               ("run_round", 49_500, 90_500)],
        ops={0: OPS})
    return SimpleNamespace(trace=trace, result={}, peaks=None, chips=1,
                           compiles_in_window=0)


READS = {"schedule_idle_ms": 0.004, "presample_idle_ms": 0.0155,
         "launch_idle_ms": 0.001, "readback_idle_ms": 0.0065,
         "padded_user_share": 12.5, "staged_mb_per_round": 2.5}


def test_layer_readers_and_the_logged_remainder(monkeypatch, capsys):
    monkeypatch.setattr(pt, "load", lambda trace_dir=None: SPANS)
    monkeypatch.setattr(pt, "_logged", None)
    run = _run()
    for name, want in READS.items():
        assert harness.layer_reader(name)(run) == pytest.approx(want), name
    err = capsys.readouterr().err.splitlines()
    # logged once: the run_round spans are 41_000 ns with 11_000 and 13_000
    # busy; the four stages hold 28_000 and 26_000 of their idle ns
    assert len(err) == 1 and err[0].startswith("[program_trace] ")
    assert "host_ms_per_round=0.029 less the four stages leaves 0.002 ms " \
        "unexplained (6.9 %)" in err[0]
    assert "of hsfl.readback, 0.005 ms follow the device's last op" in err[0]


def test_layer_readers_give_nothing_without_program_spans(monkeypatch,
                                                          capsys):
    monkeypatch.setattr(pt, "load", lambda trace_dir=None: [])
    for name in READS:
        assert harness.layer_reader(name)(_run()) is None
    assert capsys.readouterr().err == ""


def test_reads_the_spans_and_stats_of_a_cpu_trace(tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("hsfl.round", round=7):
            with jax.profiler.TraceAnnotation("hsfl.launch", users=3, K=4,
                                              staged_bytes=10 ** 10):
                jax.numpy.ones(4).block_until_ready()
        with jax.profiler.TraceAnnotation("evaluate"):
            pass
    got = pt.load(tmp_path)
    assert [(n, st) for n, _, _, st in got] == [
        ("hsfl.round", {"round": 7}),
        ("hsfl.launch", {"users": 3, "K": 4, "staged_bytes": 10 ** 10})]
    assert pt.load(tmp_path) is got
