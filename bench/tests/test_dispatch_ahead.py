"""The trainer's ``ppo.dispatch_ahead`` marks: zero-length ring entries that
leave the split of the host gap as it was, and the share of the window's
rounds that ``train.dispatch_ahead_share`` reads from them."""

import sys
from types import SimpleNamespace

import pytest

from harness import program_spans, spec
from harness.trace import DeviceTrace
from repro.core import tracing
from test_harness import _write_trace
from test_program_spans import MS, PLANTED, WS

AHEAD = "ppo.dispatch_ahead"


def _entries(planted):
    return [(n, WS + int(s * MS), WS + int(e * MS), p)
            for n, s, e, p in planted]


@pytest.fixture
def ctx(tmp_path):
    t = DeviceTrace(str(_write_trace(tmp_path)))
    ws = WS * 1e-9
    return SimpleNamespace(trace=t, program="jit_episode", rounds=2,
                           window=(ws, ws + t.window_s),
                           flops_per_round=1e12, peaks={})


def test_a_mark_in_an_idle_interval_leaves_the_gap_split_unchanged(
        ctx, monkeypatch):
    monkeypatch.setattr(tracing, "spans", lambda: _entries(PLANTED))
    before = program_spans.gap_parts(ctx)
    # marks in both idle intervals ([100, 105) and [110, 120) ms), under
    # ppo.select and ppo.dispatch, and one while the device is busy
    marks = [(AHEAD, t, t, p) for t, p in ((102.5, "ppo.select"),
                                          (113.0, "ppo.dispatch"),
                                          (150.0, "ppo.round"))]
    monkeypatch.setattr(tracing, "spans", lambda: _entries(PLANTED + marks))
    after = program_spans.gap_parts(ctx)
    assert set(after) == set(before)
    for part in program_spans.NAMES:
        assert after[part] == pytest.approx(before[part], rel=1e-12,
                                            abs=1e-15)


def test_the_share_reads_the_marks_in_the_window_over_its_rounds(
        ctx, monkeypatch):
    # four rounds in the window; three marks in it, one before, one after
    ctx.rounds = 4
    span_ms = 1e3 * ctx.trace.window_s
    marks = [(AHEAD, t, t, "ppo.round")
             for t in (-3.0, 1.0, 105.0, span_ms - 1.0, span_ms + 4.0)]
    monkeypatch.setattr(tracing, "spans", lambda: _entries(PLANTED + marks))
    read = spec.load_reader("train.dispatch_ahead_share")
    assert read(ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("why", ["program without marks",
                                 "program without spans", "no rounds"])
def test_the_share_reads_nothing_it_cannot_count(ctx, monkeypatch, why):
    if why == "program without marks":
        monkeypatch.delattr(tracing, "mark")
    elif why == "program without spans":
        import repro.core
        monkeypatch.delattr(repro.core, "tracing")
        monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    else:
        ctx.rounds = 0
    assert spec.load_reader("train.dispatch_ahead_share")(ctx) is None
