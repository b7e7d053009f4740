"""``train_ppo`` runs one round ahead: round r+1 is dispatched before round
r is read back. Pins: its ``TrainResult`` is bitwise the one a
round-at-a-time loop gives (written out below over
``ppo._make_episode_fn``), static, resampled, by batch mean and on a
convergence stop whose round in flight is dropped unread; it runs exactly
``ceil(max_episodes / n_envs)`` rounds; round r+1's dispatch starts before
round r's read-back."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Workload, ppo, tracing
from repro.core.ppo import PPOConfig, train_ppo
from repro.core.simulator import make_env_params
from repro.scenarios import sample_scenario_batch

N_ENVS = 4


def _params():
    return make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _draw(rnd):
    return Workload(tables=sample_scenario_batch(N_ENVS, seed=rnd,
                                                 horizon=30.0)[1])


def sync_train(p, cfg, resample=None, r_max=None):
    """The round-at-a-time loop: dispatch round r, read it back, select on
    it, and only then dispatch round r+1."""
    key = jax.random.PRNGKey(cfg.seed)
    k_init, key = jax.random.split(key)
    state = ppo.init_agent(k_init, cfg)
    fn = ppo._make_episode_fn(p, cfg, randomize_t0=resample is not None)
    fill = ppo._broadcast_table(
        ppo.constant_table(p.tpt, p.bw, p.duration), cfg.n_envs)
    by_mean = cfg.param_selection == "batch_mean"
    best_r = best_sel = -jnp.inf
    best_params, stagnant, converged_at = state["params"], 0, None
    history, rnd = [], 0
    while len(history) < cfg.max_episodes:
        tables = resample(rnd).tables if resample is not None else fill
        rnd += 1
        key, k = jax.random.split(key)
        state, rewards, _ = fn(state, tables, None, None, None, k)
        rewards = jax.device_get(rewards)
        if by_mean:
            if float(rewards.mean()) > best_sel:
                best_sel = float(rewards.mean())
                best_params = jax.device_get(state["params"])
                stagnant = 0
            else:
                stagnant += len(rewards)
        for r in rewards:
            history.append(float(r))
            if r > best_r:
                best_r = float(r)
                if not by_mean:
                    best_params = jax.device_get(state["params"])
                    stagnant = 0
            elif not by_mean:
                stagnant += 1
        if r_max is not None:
            if (converged_at is None and best_r >= (
                    cfg.convergence_frac * r_max * cfg.max_steps)):
                converged_at = len(history)
            if converged_at is not None and stagnant >= cfg.patience:
                break
    return {"history": history, "params": best_params,
            "episodes": len(history), "converged_at": converged_at,
            "best_reward": float(best_r)}


def _counting(monkeypatch):
    """Counts the calls of every episode program ``train_ppo`` builds."""
    calls = []
    orig = ppo._make_episode_fn

    def make(*a, **k):
        fn = orig(*a, **k)

        def episode(*args):
            calls.append(len(calls))
            return fn(*args)
        return episode

    monkeypatch.setattr(ppo, "_make_episode_fn", make)
    return calls


RUNS = {
    # the GOLDEN_HISTORY configuration
    "static": (dict(max_episodes=8), {}),
    "resampled": (dict(max_episodes=12), {"resample": _draw}),
    "batch_mean": (dict(max_episodes=12, param_selection="batch_mean"),
                   {"resample": _draw}),
    # converges on round 0 (r_max 0); stops once 4 episodes in a row bring
    # no new best, well before the budget, with a round in flight
    "converged": (dict(max_episodes=80, patience=4),
                  {"resample": _draw, "r_max": 0.0}),
}


@pytest.mark.parametrize("run", list(RUNS))
def test_result_is_bitwise_the_round_at_a_time_loop(run, monkeypatch):
    over, kw = RUNS[run]
    cfg = PPOConfig(n_envs=N_ENVS, max_steps=5, seed=0, **over)
    want = sync_train(_params(), cfg, **kw)
    drawn = []
    if "resample" in kw:
        kw = dict(kw, resample=lambda rnd: drawn.append(rnd) or _draw(rnd))
    calls = _counting(monkeypatch)
    res = train_ppo(_params(), cfg, **kw)
    assert res.history == want["history"]
    assert res.episodes == want["episodes"]
    assert res.converged_at == want["converged_at"]
    assert res.best_reward == want["best_reward"]
    got_leaves, got_def = jax.tree_util.tree_flatten(res.params)
    want_leaves, want_def = jax.tree_util.tree_flatten(want["params"])
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert np.asarray(g).dtype == np.asarray(w).dtype
        assert np.array_equal(np.asarray(g), np.asarray(w))
    read = res.episodes // N_ENVS
    if run == "converged":
        assert res.converged_at is not None
        assert res.episodes < cfg.max_episodes
        # one more round dispatched, and drawn, than read: it is dropped
        assert len(calls) == read + 1
        assert drawn == list(range(read + 1))
    else:
        assert len(calls) == read
        if drawn:
            assert drawn == list(range(read))


@pytest.mark.parametrize("max_episodes,rounds", [(10, 3), (4, 1), (3, 1),
                                                 (0, 0)])
def test_rounds_dispatched_are_the_episode_budget_rounded_up(
        max_episodes, rounds, monkeypatch):
    calls = _counting(monkeypatch)
    res = train_ppo(_params(), PPOConfig(max_episodes=max_episodes,
                                         n_envs=N_ENVS, max_steps=5, seed=0))
    assert len(calls) == rounds
    assert res.episodes == rounds * N_ENVS
    assert len(res.history) == rounds * N_ENVS


def test_round_r_plus_1_is_dispatched_before_round_r_is_read():
    t0 = time.perf_counter_ns()
    res = train_ppo(_params(), PPOConfig(max_episodes=16, n_envs=N_ENVS,
                                         max_steps=5, seed=0))
    got = [s for s in tracing.spans() if s[1] >= t0]
    start = {name: sorted(s for n, s, _, _ in got if n == name)
             for name in ("ppo.dispatch", "ppo.rewards", "ppo.select")}
    n = res.episodes // N_ENVS
    assert n == 4
    assert all(len(v) == n for v in start.values())
    d, r, s = start["ppo.dispatch"], start["ppo.rewards"], start["ppo.select"]
    # round r+1 is dispatched before round r is read back ...
    assert all(d[i + 1] < r[i] for i in range(n - 1))
    # ... and round r+2 only once round r has been selected on: one ahead
    assert all(s[i] < d[i + 2] for i in range(n - 2))
    # rounds are read and selected on in order
    assert all(r[i] < s[i] < r[i + 1] for i in range(n - 1))
