"""CPU checks of ``layers/staged_ahead_share.py`` on hand-built
``hsfl.*`` spans: the share of the window's launches whose inputs were
staged during the round before."""
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
from benchlib import harness, program_trace as pt, tracing

WINDOW = (0, 100_000)


def _launch(ahead):
    return {"users": 3, "K": 4, "staged_bytes": 2_000_000, "ahead": ahead}


# three launches inside the window (the first prepared itself), one after
SPANS = [
    ("hsfl.round", 1_000, 30_000, {"round": 1}),
    ("hsfl.schedule", 1_000, 2_000, {}),
    ("hsfl.presample", 2_000, 8_000, {}),
    ("hsfl.launch", 8_000, 9_000, _launch(0)),
    ("hsfl.schedule", 9_000, 10_000, {}),
    ("hsfl.presample", 10_000, 16_000, {}),
    ("hsfl.readback", 16_000, 29_000, {}),
    ("hsfl.round", 31_000, 60_000, {"round": 2}),
    ("hsfl.launch", 31_000, 32_000, _launch(1)),
    ("hsfl.readback", 40_000, 59_000, {}),
    ("hsfl.round", 61_000, 90_000, {"round": 3}),
    ("hsfl.launch", 61_000, 62_000, _launch(1)),
    ("hsfl.readback", 70_000, 89_000, {}),
    ("hsfl.round", 101_000, 120_000, {"round": 4}),
    ("hsfl.launch", 101_000, 102_000, _launch(0)),
]


def _run():
    trace = tracing.Trace(window=WINDOW,
                          spans=[("window", *WINDOW)],
                          ops={0: [("%fusion.1 = f32[8] fusion()", 9_000,
                                    25_000)]})
    return SimpleNamespace(trace=trace, result={}, peaks=None, chips=1,
                           compiles_in_window=0)


def _reader(monkeypatch, spans):
    monkeypatch.setattr(pt, "load", lambda trace_dir=None: spans)
    monkeypatch.setattr(pt, "_logged", None)
    return harness.layer_reader("staged_ahead_share")


def test_share_over_the_windows_launches(monkeypatch):
    # the launch after the window, which prepared itself, is not counted
    read = _reader(monkeypatch, SPANS)
    assert read(_run()) == pytest.approx(100.0 * 2 / 3)


def test_every_launch_staged_ahead_reads_100(monkeypatch):
    spans = [sp for sp in SPANS if sp[1] >= 31_000]
    assert _reader(monkeypatch, spans)(_run()) == pytest.approx(100.0)


@pytest.mark.parametrize("spans", [
    [],
    # launches without the stat, as a program that prepares each round
    # itself records them
    [(n, s, e, {k: v for k, v in st.items() if k != "ahead"})
     for n, s, e, st in SPANS],
    # no launch inside the window
    SPANS[-2:],
], ids=["no spans", "no ahead stat", "no launch in the window"])
def test_nothing_without_staged_launches(spans, monkeypatch):
    assert _reader(monkeypatch, spans)(_run()) is None
