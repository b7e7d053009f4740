"""Temporal policy stack: HistorySpec frame stacking, the GRU actor-critic,
PPOConfig(policy=...), and live/sim parity of the temporal features.

The load-bearing pins:
  * policy="mlp" / a 1-frame "stacked" policy are BIT-identical to the PR 2
    path (same goldens as tests/test_unified_env.py, atol=0).
  * AutoMDTController maintains the same zero-padded history window / GRU
    carry live from consecutive observe() dicts that the sim-side rollout
    threads through its episode scan — sim-trained params transfer
    unchanged (the temporal twin of the CONTEXT_OBS parity test).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import networks as nets
from repro.core.controller import AutoMDTController
from repro.core.ppo import PPOConfig, train_ppo, init_agent, effective_obs_spec
from repro.core.simulator import (make_env_params, env_reset, env_step,
                                  observe, ObservationSpec, HistorySpec,
                                  DEFAULT_OBS, CONTEXT_OBS, history_init,
                                  history_push, history_flatten)

# The golden of tests/test_unified_env.py (the pre-refactor static path,
# re-captured there under JAX 0.9's default RNG stream); the temporal stack
# must leave it untouched.
from tests.test_unified_env import GOLDEN_HISTORY


def _params():
    return make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _params_fill():
    return make_env_params(tpt=[0.2, 0.05, 0.2], bw=[2, 2, 2],
                           cap=[0.5, 0.5], n_max=50)


def _obs_dict(p, s):
    return {"threads": list(np.asarray(s.threads)),
            "throughputs": list(np.asarray(s.throughputs)),
            "sender_free": float(p.cap[0] - s.buffers[0]),
            "receiver_free": float(p.cap[1] - s.buffers[1]),
            "sender_capacity": float(p.cap[0]),
            "receiver_capacity": float(p.cap[1])}


# ---------------------------------------------------------------------------
# HistorySpec + history helpers
# ---------------------------------------------------------------------------

def test_history_spec_dims():
    assert HistorySpec(4).dim == 32 and HistorySpec(4).frame_dim == 8
    assert HistorySpec(4, context=True).dim == 52
    assert HistorySpec(1, context=True) == CONTEXT_OBS
    assert ObservationSpec(context=True, history=3).dim == 39
    assert DEFAULT_OBS.history == 1 and DEFAULT_OBS.dim == 8


def test_history_helpers_zero_pad_and_push():
    spec = HistorySpec(3)
    f0 = jnp.arange(8.0)
    hist = history_init(spec, f0)
    assert hist.shape == (3, 8)
    np.testing.assert_array_equal(np.asarray(hist[:2]), np.zeros((2, 8)))
    np.testing.assert_array_equal(np.asarray(hist[2]), np.asarray(f0))
    f1 = f0 + 100.0
    hist = history_push(hist, f1)
    np.testing.assert_array_equal(np.asarray(hist[0]), np.zeros(8))
    np.testing.assert_array_equal(np.asarray(hist[1]), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(hist[2]), np.asarray(f1))
    flat = history_flatten(hist)
    assert flat.shape == (24,)
    np.testing.assert_array_equal(np.asarray(flat[8:16]), np.asarray(f0))


def test_one_frame_history_is_identity():
    """K=1 is exactly the unstacked path — the bit-identity foundation."""
    spec = HistorySpec(1)
    f = jnp.arange(8.0) * 0.37
    hist = history_init(spec, f)
    np.testing.assert_array_equal(np.asarray(history_flatten(hist)),
                                  np.asarray(f))
    f2 = f + 1.0
    np.testing.assert_array_equal(
        np.asarray(history_flatten(history_push(hist, f2))), np.asarray(f2))


# ---------------------------------------------------------------------------
# Golden pins: the temporal stack leaves the PR 2 path bit-identical
# ---------------------------------------------------------------------------

def test_mlp_policy_reproduces_pre_refactor_goldens():
    res = train_ppo(_params(),
                    PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0,
                              policy="mlp"))
    np.testing.assert_allclose(res.history, GOLDEN_HISTORY, atol=1e-4)


def test_stacked_one_frame_bit_identical_to_mlp():
    """policy="stacked" with history=1 is the SAME trace as policy="mlp":
    identical key stream, identical arithmetic, atol=0."""
    cfg_mlp = PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0)
    cfg_st1 = PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0,
                        policy="stacked", history=1)
    a = train_ppo(_params(), cfg_mlp)
    b = train_ppo(_params(), cfg_st1)
    np.testing.assert_allclose(a.history, b.history, atol=0)
    np.testing.assert_allclose(b.history, GOLDEN_HISTORY, atol=1e-4)


# ---------------------------------------------------------------------------
# PPOConfig policy selection
# ---------------------------------------------------------------------------

def test_effective_obs_spec():
    assert effective_obs_spec(PPOConfig()) == DEFAULT_OBS
    st = PPOConfig(policy="stacked", history=4, obs_spec=CONTEXT_OBS)
    assert effective_obs_spec(st) == ObservationSpec(context=True, history=4)
    assert effective_obs_spec(st).dim == 52
    # an explicit HistorySpec wins over cfg.history
    ex = PPOConfig(policy="stacked", history=4, obs_spec=HistorySpec(2))
    assert effective_obs_spec(ex).history == 2
    # gru consumes the spec as given (frame-level by default)
    assert effective_obs_spec(PPOConfig(policy="gru",
                                        obs_spec=CONTEXT_OBS)).dim == 13


def test_unknown_policy_raises():
    with pytest.raises(ValueError, match="policy"):
        init_agent(jax.random.PRNGKey(0), PPOConfig(policy="lstm"))


def test_init_agent_widths_follow_policy():
    cfg = PPOConfig(policy="stacked", history=4, obs_spec=CONTEXT_OBS)
    ag = init_agent(jax.random.PRNGKey(0), cfg)
    assert ag["params"]["policy"]["embed"]["w"].shape[0] == 52
    g = init_agent(jax.random.PRNGKey(0),
                   PPOConfig(policy="gru", obs_spec=CONTEXT_OBS,
                             rnn_hidden=32))
    assert g["params"]["policy"]["embed"]["w"].shape[0] == 13
    assert "gru" in g["params"]["policy"]
    assert nets.rnn_carry(g["params"]["policy"]).shape == (32,)


def test_stacked_training_smoke():
    cfg = PPOConfig(max_episodes=4, n_envs=2, max_steps=3, seed=0,
                    policy="stacked", history=4, obs_spec=CONTEXT_OBS)
    res = train_ppo(_params(), cfg)
    assert res.episodes == 4
    assert np.isfinite(res.history).all()
    mean, _ = nets.policy_apply(res.params["policy"], jnp.zeros((52,)))
    assert mean.shape == (3,)


def test_gru_training_smoke_and_carry():
    cfg = PPOConfig(max_episodes=4, n_envs=2, max_steps=3, seed=0,
                    policy="gru", obs_spec=CONTEXT_OBS)
    res = train_ppo(_params(), cfg)
    assert res.episodes == 4
    assert np.isfinite(res.history).all()
    pol = res.params["policy"]
    h0 = nets.rnn_carry(pol)
    h1, mean, std = nets.rnn_policy_apply(pol, h0, jnp.zeros((13,)))
    assert h1.shape == h0.shape and mean.shape == (3,)
    # the carry actually carries: same input, different carry, different out
    h2, mean2, _ = nets.rnn_policy_apply(pol, h1, jnp.zeros((13,)))
    assert not np.allclose(np.asarray(mean), np.asarray(mean2))


def test_gru_cell_batch_broadcast():
    p = nets.gru_init(jax.random.PRNGKey(0), 8, 16)
    h = jnp.zeros((5, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 8))
    out = nets.gru_cell(p, h, x)
    assert out.shape == (5, 16)
    one = nets.gru_cell(p, h[0], x[0])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(one), atol=1e-6)


# ---------------------------------------------------------------------------
# Live/sim parity: the controller is the live twin of the rollout
# ---------------------------------------------------------------------------

def _state_sequence(p, n=4):
    states = [env_reset(p, jax.random.PRNGKey(2))]
    for a in ([8, 4, 2], [10, 6, 3], [5, 5, 5], [12, 2, 7])[:n]:
        st, _, _ = env_step(p, states[-1], jnp.asarray(a, jnp.float32))
        states.append(st)
    return states


def test_history_stacking_live_sim_parity():
    """The same observation sequence through the sim-side history helpers
    and through AutoMDTController produces identical stacked features."""
    p = _params_fill()
    spec = HistorySpec(3, context=True)
    states = _state_sequence(p)
    frames = [observe(p, s, spec=CONTEXT_OBS) for s in states]
    hist = history_init(spec, frames[0])
    sim_vecs = [history_flatten(hist)]
    for f in frames[1:]:
        hist = history_push(hist, f)
        sim_vecs.append(history_flatten(hist))

    policy = nets.policy_init(jax.random.PRNGKey(0), obs_dim=spec.dim)
    ctrl = AutoMDTController(policy, n_max=float(p.n_max),
                             bw_ref=float(np.max(np.asarray(p.bw))),
                             obs_spec=spec, deterministic=True)
    for st, want in zip(states, sim_vecs):
        vec = ctrl._obs_vector(_obs_dict(p, st))
        assert vec.shape == (spec.dim,)
        np.testing.assert_allclose(np.asarray(vec), np.asarray(want),
                                   atol=1e-5)


def test_gru_carry_live_sim_parity():
    """Consecutive controller.step() calls thread the same zero-initialized
    GRU carry the training scan threads: identical actions."""
    p = _params_fill()
    states = _state_sequence(p)
    frames = [observe(p, s, spec=CONTEXT_OBS) for s in states]
    pol = nets.rnn_policy_init(jax.random.PRNGKey(1), obs_dim=CONTEXT_OBS.dim)
    ctrl = AutoMDTController(pol, n_max=float(p.n_max),
                             bw_ref=float(np.max(np.asarray(p.bw))),
                             obs_spec=CONTEXT_OBS, deterministic=True,
                             policy="gru")
    h = nets.rnn_carry(pol)
    for st, f in zip(states, frames):
        h, mean, _ = nets.rnn_policy_apply(pol, h, f)
        want = tuple(np.clip(np.round(np.asarray(mean)), 1,
                             float(p.n_max)).astype(int).tolist())
        assert ctrl.step(_obs_dict(p, st)) == want


def test_controller_reset_clears_temporal_state():
    p = _params_fill()
    spec = HistorySpec(3, context=True)
    states = _state_sequence(p, n=2)
    policy = nets.policy_init(jax.random.PRNGKey(0), obs_dim=spec.dim)
    ctrl = AutoMDTController(policy, n_max=float(p.n_max), bw_ref=2.0,
                             obs_spec=spec, deterministic=True)
    first = np.asarray(ctrl._obs_vector(_obs_dict(p, states[0])))
    ctrl._obs_vector(_obs_dict(p, states[1]))
    ctrl.reset()
    assert ctrl._hist is None and ctrl._carry is None
    again = np.asarray(ctrl._obs_vector(_obs_dict(p, states[0])))
    np.testing.assert_allclose(again, first, atol=0)

    gctrl = AutoMDTController(
        nets.rnn_policy_init(jax.random.PRNGKey(1), obs_dim=13),
        n_max=float(p.n_max), bw_ref=2.0, obs_spec=CONTEXT_OBS,
        deterministic=True, policy="gru")
    a0 = gctrl.step(_obs_dict(p, states[0]))
    gctrl.step(_obs_dict(p, states[1]))
    gctrl.reset()
    assert gctrl.step(_obs_dict(p, states[0])) == a0
