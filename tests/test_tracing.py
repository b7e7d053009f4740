"""The trainer's host spans (``repro.core.tracing``): nesting and parent
links, the ring's bound, spans that close on an exception, marks, the
per-round phases of ``train_ppo``, and the episode program's named
scopes."""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import ppo, tracing
from repro.core.ppo import PPOConfig, train_ppo
from repro.core.simulator import make_env_params
from tests.test_unified_env import GOLDEN_HISTORY


def _since(t0):
    return [s for s in tracing.spans() if s[1] >= t0]


def test_spans_nest_and_link_their_parents():
    t0 = time.perf_counter_ns()
    with tracing.span("outer", round=3):
        with tracing.span("a"):
            pass
        with tracing.span("b"):
            with tracing.span("c"):
                pass
    got = _since(t0)
    # a span is recorded as it closes: children before their parent
    assert [(n, p) for n, _, _, p in got] == [
        ("a", "outer"), ("c", "b"), ("b", "outer"), ("outer", None)]
    by = {n: (s, e) for n, s, e, _ in got}
    for child, parent in (("a", "outer"), ("b", "outer"), ("c", "b")):
        assert by[parent][0] <= by[child][0] <= by[child][1] \
            <= by[parent][1]
    assert by["a"][1] <= by["b"][0]


def test_parents_are_per_thread():
    def worker():
        with tracing.span("worker"):
            pass

    t0 = time.perf_counter_ns()
    with tracing.span("main"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert {n: p for n, _, _, p in _since(t0)} == {"worker": None,
                                                   "main": None}


def test_the_ring_keeps_the_newest_spans_up_to_its_bound():
    n = tracing.RING_SIZE + 10
    for i in range(n):
        with tracing.span(f"s{i}"):
            pass
    got = tracing.spans()
    assert len(got) == tracing.RING_SIZE
    assert got[-1][0] == f"s{n - 1}"
    assert got[0][0] == f"s{n - tracing.RING_SIZE}"
    # a copy: the caller cannot change the ring
    got.clear()
    assert len(tracing.spans()) == tracing.RING_SIZE


def test_a_span_records_when_its_block_raises():
    t0 = time.perf_counter_ns()
    with tracing.span("outer"):
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("inside")
        with tracing.span("after"):
            pass
    got = _since(t0)
    assert [(n, p) for n, _, _, p in got] == [
        ("boom", "outer"), ("after", "outer"), ("outer", None)]
    assert all(e >= s for _, s, e, _ in got)


def test_spans_land_in_a_profiler_trace(tmp_path):
    """The second job of a span: a ``TraceAnnotation`` on the trace's
    clock, the round a step-view event."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("ppo.round", step_num=7):
            with tracing.span("ppo.dispatch"):
                jax.block_until_ready(jax.numpy.ones(3) + 1)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = {ev.name for plane in ProfileData.from_file(files[0]).planes
             for line in plane.lines for ev in line.events}
    assert "ppo.dispatch" in names
    assert any(n.startswith("ppo.round") for n in names)


def test_a_mark_is_a_zero_length_entry_under_its_parent():
    t0 = time.perf_counter_ns()
    tracing.mark("top")
    with tracing.span("outer"):
        tracing.mark("ping")
        with tracing.span("inner"):
            pass
    got = _since(t0)
    assert [(n, p) for n, _, _, p in got] == [
        ("top", None), ("ping", "outer"), ("inner", "outer"),
        ("outer", None)]
    by = {n: (s, e) for n, s, e, _ in got}
    assert by["ping"][0] == by["ping"][1]
    assert by["outer"][0] <= by["ping"][0] <= by["inner"][0]


def test_a_mark_lands_in_a_profiler_trace_with_its_ring_entry(tmp_path):
    from jax.profiler import ProfileData
    t0 = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("ppo.round", step_num=3):
            tracing.mark("ppo.dispatch_ahead")
    finally:
        jax.profiler.stop_trace()
    assert [(n, s == e, p) for n, s, e, p in _since(t0)] == [
        ("ppo.dispatch_ahead", True, "ppo.round"),
        ("ppo.round", False, None)]
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = {ev.name for plane in ProfileData.from_file(files[0]).planes
             for line in plane.lines for ev in line.events}
    assert "ppo.dispatch_ahead" in names


def _params():
    return make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _cfg():
    return PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0)


@pytest.fixture(scope="module")
def tiny_run():
    t0 = time.perf_counter_ns()
    res = train_ppo(_params(), _cfg())
    return res, _since(t0)


def test_train_ppo_spans_each_round_in_its_phases(tiny_run):
    """Round r's span holds round r+1's dispatch (round 0's holds its own
    first), then round r's read-back and selection."""
    res, got = tiny_run
    rounds = sorted((s, e) for n, s, e, _ in got if n == "ppo.round")
    assert len(rounds) == res.episodes // _cfg().n_envs == 2
    for r, (s, e) in enumerate(rounds):
        inside = sorted((cs, n, p) for n, cs, ce, p in got
                        if s <= cs and ce <= e and n != "ppo.round")
        phases = [(n, p) for _, n, p in inside
                  if p == "ppo.round" and n != "ppo.dispatch_ahead"]
        dispatches = (r == 0) + (r + 1 < len(rounds))
        assert phases == [("ppo.dispatch", "ppo.round")] * dispatches + [
            ("ppo.rewards", "ppo.round"), ("ppo.select", "ppo.round")]
        # every best-param copy lies in the selection
        assert all(p == "ppo.select" for _, n, p in inside
                   if n == "ppo.best_copy")
    copies = [n for n, *_ in got if n == "ppo.best_copy"]
    # the first round always finds a best; 8 episodes give at most 8
    assert 1 <= len(copies) <= 8
    # a dispatch ahead is marked, as an instant in the round, just before
    # the dispatch of a round after the first
    marks = [(s, e, p) for n, s, e, p in got if n == "ppo.dispatch_ahead"]
    assert len(marks) <= len(rounds) - 1
    dispatch = sorted(s for n, s, _, _ in got if n == "ppo.dispatch")
    for s, e, p in marks:
        assert s == e and p == "ppo.round"
        assert s <= dispatch[-1] and s > dispatch[0]


def test_train_ppo_history_is_unchanged_by_its_spans(tiny_run):
    res, _ = tiny_run
    np.testing.assert_allclose(res.history, GOLDEN_HISTORY, atol=1e-4)


def test_the_episode_program_names_rollout_and_update():
    p, cfg = _params(), _cfg()
    fn = ppo._make_episode_fn(p, cfg, randomize_t0=False)
    state = ppo.init_agent(jax.random.PRNGKey(0), cfg)
    tables = ppo._broadcast_table(
        ppo.constant_table(p.tpt, p.bw, p.duration), cfg.n_envs)
    text = fn.lower(state, tables, None, None, None,
                    jax.random.PRNGKey(1)).as_text(debug_info=True)
    assert "jit(episode)/rollout/" in text
    assert "jit(episode)/update/" in text
