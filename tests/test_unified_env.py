"""Schedule-native environment core: the unified Env API must reproduce the
pre-refactor static path bit-for-bit (goldens captured at PR 1 HEAD), a 1-bin
table must reproduce the frozen conditions exactly, ObservationSpec must flow
through networks/ppo/controller, and the two substep backends must agree."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import networks as nets
from repro.core.controller import AutoMDTController
from repro.core.ppo import PPOConfig, train_ppo
from repro.core.schedule import constant_table, make_table
from repro.core.simulator import (make_env_params, sim_interval, env_reset,
                                  env_step, ObservationSpec, DEFAULT_OBS,
                                  CONTEXT_OBS, OBS_DIM, CONTEXT_DIM)

# ---------------------------------------------------------------------------
# Goldens captured from the PRE-refactor static path (PR 1 HEAD, seed repo
# dual-stack code) — the unified schedule-native core must reproduce them.
# GOLDEN_HISTORY and GOLDEN_RESET_THREADS draw on the PRNG key stream: they
# were re-captured under JAX 0.9's default stream
# (jax_threefry_partitionable=True) after checking that the earlier values
# ([9.479823, ...] and [6, 14, 8]) still reproduced exactly with
# jax_threefry_partitionable=False — only the key stream changed.
# ---------------------------------------------------------------------------

# train_ppo on tpt=[0.08,0.16,0.2], bw=1, cap=2, n_max=50,
# PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0)
GOLDEN_HISTORY = [8.858108, 9.023705, 9.158712, 8.839299,
                  8.916402, 8.633703, 8.926790, 9.131025]

# 3x sim_interval on tpt=[0.2,0.05,0.2], bw=2, cap=0.5, threads=[8,4,2]
GOLDEN_BUFS = [0.4959999918937683, 0.0]
GOLDEN_TPS = [0.20000040531158447, 0.20000000298023224, 0.20000000298023224]

# env_reset(PRNGKey(42)) + env_step([9,9,9]) on the train_ppo params above
GOLDEN_RESET_THREADS = [10.0, 10.0, 7.0]
GOLDEN_OBS = [0.18, 0.18, 0.18, 0.72, 0.72, 0.72, 1.0, 1.0]
GOLDEN_REWARD = 1.807391


def _params_read():
    return make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _params_fill():
    return make_env_params(tpt=[0.2, 0.05, 0.2], bw=[2, 2, 2],
                           cap=[0.5, 0.5], n_max=50)


def test_unified_train_ppo_reproduces_pre_refactor_goldens():
    """Satellite pin: train_ppo(tables=None) on a static config produces the
    SAME rollout rewards as the old dedicated static trainer (same seeds,
    same key stream, same arithmetic)."""
    res = train_ppo(_params_read(),
                    PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0))
    np.testing.assert_allclose(res.history, GOLDEN_HISTORY, atol=1e-4)


def test_static_sim_interval_matches_golden():
    p = _params_fill()
    bufs = jnp.zeros(2)
    threads = jnp.asarray([8.0, 4.0, 2.0])
    for _ in range(3):
        bufs, tps = sim_interval(p, bufs, threads)
    np.testing.assert_allclose(np.asarray(bufs), GOLDEN_BUFS, atol=1e-6)
    np.testing.assert_allclose(np.asarray(tps), GOLDEN_TPS, atol=1e-6)


def test_one_bin_table_reproduces_static_exactly():
    """Satellite pin: a 1-bin ScheduleTable built from the params IS the
    static path — zero tolerance."""
    p = _params_fill()
    tab = constant_table(p.tpt, p.bw, p.duration)
    bufs_s = jnp.zeros(2)
    bufs_t = jnp.zeros(2)
    threads = jnp.asarray([8.0, 4.0, 2.0])
    t = jnp.zeros(())
    for _ in range(4):
        bufs_s, tps_s = sim_interval(p, bufs_s, threads)
        bufs_t, tps_t = sim_interval(p, bufs_t, threads, t, table=tab)
        t = t + p.duration
        assert np.array_equal(np.asarray(bufs_s), np.asarray(bufs_t))
        assert np.array_equal(np.asarray(tps_s), np.asarray(tps_t))


def test_env_step_matches_golden_obs_and_reward():
    p = _params_read()
    st = env_reset(p, jax.random.PRNGKey(42))
    assert np.asarray(st.threads).tolist() == GOLDEN_RESET_THREADS
    st2, obs, r = env_step(p, st, jnp.asarray([9.0, 9.0, 9.0]))
    np.testing.assert_allclose(np.asarray(obs), GOLDEN_OBS, atol=1e-5)
    assert float(r) == pytest.approx(GOLDEN_REWARD, abs=1e-5)


def test_batch_mean_selection_same_history_different_params():
    """param_selection only changes WHICH params are kept (lower-variance
    batch-mean estimate under domain randomization), never the training
    trajectory: history is identical between modes."""
    from repro.scenarios import sample_scenario_batch
    p = _params_read()
    _, tables = sample_scenario_batch(4, seed=0, horizon=30.0)
    a = train_ppo(p, PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0),
                  tables=tables)
    b = train_ppo(p, PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0,
                               param_selection="batch_mean"), tables=tables)
    np.testing.assert_allclose(a.history, b.history, atol=0)


# ---------------------------------------------------------------------------
# ObservationSpec
# ---------------------------------------------------------------------------

def test_observation_spec_dims():
    assert DEFAULT_OBS.dim == OBS_DIM == 8
    assert CONTEXT_OBS.dim == OBS_DIM + CONTEXT_DIM == 13
    assert ObservationSpec(context=True).dim == 13


def test_context_obs_extends_base_obs():
    """First 8 dims identical to the base spec; the 5 context dims carry the
    throughput deltas and buffer-drain rates."""
    p = _params_fill()
    st = env_reset(p, jax.random.PRNGKey(1))
    st2, obs_base, _ = env_step(p, st, jnp.asarray([8.0, 4.0, 2.0]))
    _, obs_ctx, _ = env_step(p, st, jnp.asarray([8.0, 4.0, 2.0]),
                             spec=CONTEXT_OBS)
    obs_base = np.asarray(obs_base)
    obs_ctx = np.asarray(obs_ctx)
    assert obs_ctx.shape == (13,)
    np.testing.assert_allclose(obs_ctx[:8], obs_base, atol=1e-6)
    tps = np.asarray(st2.throughputs)
    prev = np.asarray(st2.prev_throughputs)
    bw_ref = float(np.max(np.asarray(p.bw)))
    np.testing.assert_allclose(obs_ctx[8:11], (tps - prev) / bw_ref,
                               atol=1e-6)
    cap = np.asarray(p.cap)
    np.testing.assert_allclose(
        obs_ctx[11:],
        [(tps[1] - tps[0]) / cap[0], (tps[2] - tps[1]) / cap[1]], atol=1e-6)


def test_context_spec_flows_through_networks_and_training():
    p = _params_read()
    cfg = PPOConfig(max_episodes=4, n_envs=2, max_steps=3, seed=0,
                    obs_spec=CONTEXT_OBS)
    res = train_ppo(p, cfg)
    assert res.episodes == 4
    assert np.isfinite(res.history).all()
    mean, std = nets.policy_apply(res.params["policy"], jnp.zeros((13,)))
    assert mean.shape == (3,)


def test_controller_context_obs_is_live_twin_of_sim_observe():
    """AutoMDTController with CONTEXT_OBS builds the same 13-dim vector from
    consecutive observe() dicts that the simulator derives from EnvState."""
    p = _params_fill()
    st = env_reset(p, jax.random.PRNGKey(2))
    st2, obs_sim, _ = env_step(p, st, jnp.asarray([8.0, 4.0, 2.0]),
                               spec=CONTEXT_OBS)
    policy = nets.policy_init(jax.random.PRNGKey(0), obs_dim=13)
    ctrl = AutoMDTController(policy, n_max=float(p.n_max),
                             bw_ref=float(np.max(np.asarray(p.bw))),
                             obs_spec=CONTEXT_OBS, deterministic=True)

    def obs_dict(s):
        return {"threads": list(np.asarray(s.threads)),
                "throughputs": list(np.asarray(s.throughputs)),
                "sender_free": float(p.cap[0] - s.buffers[0]),
                "receiver_free": float(p.cap[1] - s.buffers[1]),
                "sender_capacity": float(p.cap[0]),
                "receiver_capacity": float(p.cap[1])}

    ctrl._obs_vector(obs_dict(st))          # primes prev throughputs
    vec = ctrl._obs_vector(obs_dict(st2))
    np.testing.assert_allclose(np.asarray(vec), np.asarray(obs_sim),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Substep backends
# ---------------------------------------------------------------------------

def test_backends_agree_on_interval():
    """jnp scan vs Pallas kernel (interpret mode on non-TPU hosts): same
    precomputed rates, same dynamics, float-tolerance agreement — static and
    scheduled."""
    p = _params_fill()
    tab = make_table(np.asarray([[0.2, 0.05, 0.2], [0.1, 0.02, 0.1]],
                                np.float32) * 1.0,
                     np.full((2, 3), 2.0, np.float32), bin_seconds=2.0)
    threads = jnp.asarray([8.0, 4.0, 2.0])
    for table in (None, tab):
        bufs_j = jnp.zeros(2)
        bufs_p = jnp.zeros(2)
        t = jnp.zeros(())
        for _ in range(3):
            bufs_j, tps_j = sim_interval(p, bufs_j, threads, t, table=table,
                                         backend="jnp")
            bufs_p, tps_p = sim_interval(p, bufs_p, threads, t, table=table,
                                         backend="pallas")
            t = t + p.duration
            np.testing.assert_allclose(np.asarray(bufs_j), np.asarray(bufs_p),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(tps_j), np.asarray(tps_p),
                                       atol=1e-5)


def test_backends_agree_under_vmap_training_step():
    """The pallas backend survives vmap over a scenario batch (the training
    data path) and matches the jnp backend."""
    from repro.scenarios import sample_scenario_batch
    p = _params_read()
    _, tables = sample_scenario_batch(4, seed=7, horizon=20.0)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    acts = jnp.full((4, 3), 8.0)

    def run(backend):
        states = jax.vmap(
            lambda tab, k: env_reset(p, k, table=tab, backend=backend)
        )(tables, keys)
        _, obs, rew = jax.vmap(
            lambda tab, st, a: env_step(p, st, a, table=tab, backend=backend)
        )(tables, states, acts)
        return np.asarray(obs), np.asarray(rew)

    obs_j, rew_j = run("jnp")
    obs_p, rew_p = run("pallas")
    np.testing.assert_allclose(obs_j, obs_p, atol=1e-5)
    np.testing.assert_allclose(rew_j, rew_p, atol=1e-4)


def test_unknown_backend_raises():
    p = _params_fill()
    with pytest.raises(ValueError, match="backend"):
        sim_interval(p, jnp.zeros(2), jnp.ones(3), backend="tpu2000")


def test_deprecated_pr1_aliases_are_gone():
    """The PR 1 dual-stack shims reached their one-cycle deprecation horizon
    and are removed: the unified ``table=`` API is the only path."""
    import repro.core.simulator as sim
    import repro.core.ppo as ppo
    for name in ("sim_interval_sched", "observe_sched", "dyn_env_reset",
                 "dyn_env_step", "DynSimEnv", "DynEnvState"):
        assert not hasattr(sim, name), name
    assert not hasattr(ppo, "train_ppo_scenarios")
