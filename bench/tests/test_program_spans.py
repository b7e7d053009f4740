"""The host gap between episode programs split among the trainer's own
spans: a small recorded trace and a planted ring of spans."""

import sys
from types import SimpleNamespace

import pytest

from harness import program_spans, spec
from harness.trace import DeviceTrace
from repro.core import tracing
from test_harness import _write_trace

MS = 1_000_000          # ns
WS = 5_000_000 * MS     # the window's start on perf_counter, in ns

# (name, start ms, end ms, parent) after the window's start. The trace's
# idle intervals between its two episode runs are [100, 105) and
# [110, 120) ms: select 4 + round 1 | round 1 + dispatch 5 + rewards 2 +
# select 1 + best_copy 1 (ms).
PLANTED = [
    ("ppo.dispatch", 2, 95, "ppo.round"),
    ("ppo.select", 90, 104, "ppo.round"),
    ("ppo.round", 0, 105, None),
    ("ppo.dispatch", 111, 116, "ppo.round"),
    ("ppo.rewards", 116, 118, "ppo.round"),
    ("ppo.best_copy", 119, 120, "ppo.select"),
    ("ppo.select", 118, 125, "ppo.round"),
    ("ppo.round", 105, 230, None),
]
EXPECT_MS = {"dispatch": 5.0, "rewards": 2.0, "select": 6.0, "other": 2.0}


def _ring(shift_ms=0.0):
    return [(n, WS + int((s + shift_ms) * MS), WS + int((e + shift_ms) * MS),
             p) for n, s, e, p in PLANTED]


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    t = DeviceTrace(str(_write_trace(tmp_path)))
    monkeypatch.setattr(tracing, "spans", _ring)
    ws = WS * 1e-9
    return SimpleNamespace(trace=t, program="jit_episode", rounds=2,
                           window=(ws, ws + t.window_s),
                           flops_per_round=1e12, peaks={})


def _read_gaps(ctx):
    return {part: spec.load_reader(f"train.gap_{part}_ms")(ctx)
            for part in program_spans.NAMES}


def test_the_four_parts_sum_to_the_host_gap(ctx):
    got = _read_gaps(ctx)
    for part, ms in EXPECT_MS.items():
        assert got[part] == pytest.approx(ms, abs=1e-6)
    gaps = ctx.trace.idle_between("jit_episode")
    assert sum(got.values()) == pytest.approx(1e3 * sum(gaps) / len(gaps),
                                              rel=1e-9)
    assert sum(got.values()) == pytest.approx(
        spec.load_reader("train.host_gap_ms")(ctx), rel=1e-9)


def test_the_clock_mapping_puts_a_span_where_it_was_planted(ctx):
    # the window is 0.4 ms longer on perf_counter than in the trace: the
    # two edges share the difference
    ws, we = ctx.window
    ctx.window = (ws, we + 0.4e-3)
    mapped = program_spans.on_trace_clock(ctx)
    want = [(s * 1e-3, e * 1e-3, n) for n, s, e, _ in PLANTED]
    assert [n for _, _, n in mapped] == [n for _, _, n in want]
    for (s, e, _), (ws_, we_, _) in zip(mapped, want):
        assert s == pytest.approx(ws_ - 0.2e-3, abs=1e-7)
        assert e == pytest.approx(we_ - 0.2e-3, abs=1e-7)


def test_spans_outside_the_window_are_left_out(ctx, monkeypatch):
    monkeypatch.setattr(tracing, "spans", lambda: _ring(shift_ms=-1000.0))
    assert program_spans.on_trace_clock(ctx) == []
    # no span open in the gaps: all of it is "other"
    got = _read_gaps(ctx)
    assert got["other"] == pytest.approx(15.0, abs=1e-6)
    assert got["dispatch"] == got["rewards"] == got["select"] == 0.0


def test_split_takes_the_innermost_span_by_exact_overlap():
    spans = [(0.0, 10.0, "outer"), (2.0, 4.0, "mid"), (2.5, 3.0, "in")]
    got = program_spans.split(spans, 1.0, 12.0)
    assert got == {"outer": pytest.approx(7.0), "mid": pytest.approx(1.5),
                   "in": pytest.approx(0.5), None: pytest.approx(2.0)}


@pytest.mark.parametrize("why", ["empty ring", "window lengths disagree",
                                 "program without spans", "no episode"])
def test_the_readers_read_nothing_they_cannot_map(ctx, monkeypatch, why):
    if why == "empty ring":
        monkeypatch.setattr(tracing, "spans", lambda: [])
    elif why == "window lengths disagree":
        ws, we = ctx.window
        ctx.window = (ws, we + 1.5e-3)
    elif why == "program without spans":
        import repro.core
        monkeypatch.delattr(repro.core, "tracing")
        monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    else:
        ctx.program = "jit_other"
    assert all(v is None for v in _read_gaps(ctx).values())
