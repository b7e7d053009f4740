"""Multi-flow fleet core: the F=1 fleet path must be BIT-identical to the
single-flow env (the PR 2 goldens, atol=0), the contention model must
conserve and split the scheduled capacity thread-proportionally, arrivals
must gate activity, one shared policy must train over a fleet (all three
temporal policies), and the live FleetController must build the exact
observation matrix the sim derives (live/sim parity)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import networks as nets
from repro.core.controller import AutoMDTController, FleetPolicy, \
    FleetController
from repro.core.fleet import (FlowSchedule, make_flow_schedule, always_on,
                              stack_flow_schedules, active_at, fleet_reset,
                              fleet_step, fleet_observe, fleet_interval,
                              fleet_achievable, jain_index,
                              _fleet_substep_rates)
from repro.core.ppo import PPOConfig, train_ppo
from repro.core.schedule import make_table, constant_table
from repro.core.simulator import (make_env_params, env_reset, env_step,
                                  observe, sim_interval, ObservationSpec,
                                  DEFAULT_OBS, CONTEXT_OBS, FLEET_OBS,
                                  OBS_DIM, CONTEXT_DIM, FLEET_DIM)

# the single-flow goldens (tests/test_unified_env.py, where the reset
# threads were re-captured under JAX 0.9's default RNG stream) — the F=1
# fleet path must reproduce them through the contention code path
GOLDEN_RESET_THREADS = [10.0, 10.0, 7.0]
GOLDEN_OBS = [0.18, 0.18, 0.18, 0.72, 0.72, 0.72, 1.0, 1.0]
GOLDEN_REWARD = 1.807391


def _params_read():
    return make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _params_base():
    return make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _sched_table():
    return make_table(np.asarray([[0.2, 0.05, 0.2], [0.1, 0.02, 0.1]],
                                 np.float32),
                      np.full((2, 3), 2.0, np.float32), bin_seconds=2.0)


def _obs_dict(p, threads, tps, buffers):
    return {"threads": list(np.asarray(threads)),
            "throughputs": list(np.asarray(tps)),
            "sender_free": float(p.cap[0] - buffers[0]),
            "receiver_free": float(p.cap[1] - buffers[1]),
            "sender_capacity": float(p.cap[0]),
            "receiver_capacity": float(p.cap[1])}


# ---------------------------------------------------------------------------
# F=1 bit-identity (atol=0) — the acceptance pin
# ---------------------------------------------------------------------------

def test_f1_reset_bit_identical_to_env_reset():
    p = _params_read()
    key = jax.random.PRNGKey(42)
    st = env_reset(p, key)
    fst = fleet_reset(p, key, 1)
    assert np.asarray(fst.threads[0]).tolist() == GOLDEN_RESET_THREADS
    for a, b in ((st.buffers, fst.buffers[0]),
                 (st.threads, fst.threads[0]),
                 (st.throughputs, fst.throughputs[0]),
                 (st.prev_throughputs, fst.prev_throughputs[0])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(st.t) == float(fst.t)


@pytest.mark.parametrize("table", [None, "sched"])
def test_f1_step_bit_identical_to_env_step(table):
    tab = _sched_table() if table == "sched" else None
    p = _params_read()
    key = jax.random.PRNGKey(42)
    st = env_reset(p, key, table=tab)
    fst = fleet_reset(p, key, 1, table=tab)
    a = jnp.asarray([9.0, 9.0, 9.0])
    for spec in (DEFAULT_OBS, CONTEXT_OBS):
        st2, obs, r = env_step(p, st, a, table=tab, spec=spec)
        fst2, fobs, fr = fleet_step(p, fst, a[None], table=tab, spec=spec)
        assert np.array_equal(np.asarray(st2.buffers),
                              np.asarray(fst2.buffers[0]))
        assert np.array_equal(np.asarray(st2.throughputs),
                              np.asarray(fst2.throughputs[0]))
        assert np.array_equal(np.asarray(obs), np.asarray(fobs[0]))
        assert float(r) == float(fr)
    if tab is None:  # the PR 2 static goldens, through the fleet path
        _, fobs, fr = fleet_step(p, fleet_reset(p, key, 1), a[None])
        np.testing.assert_allclose(np.asarray(fobs[0]), GOLDEN_OBS,
                                   atol=1e-5)
        assert float(fr) == pytest.approx(GOLDEN_REWARD, abs=1e-5)


def test_f1_observe_bit_identical():
    p = _params_read()
    st = env_reset(p, jax.random.PRNGKey(3))
    from repro.core.fleet import FleetState
    fst = FleetState(buffers=st.buffers[None], threads=st.threads[None],
                     throughputs=st.throughputs[None], t=st.t,
                     prev_throughputs=st.prev_throughputs[None])
    for spec in (DEFAULT_OBS, CONTEXT_OBS):
        o = observe(p, st, spec=spec)
        fo = fleet_observe(p, fst, flows=always_on(1), spec=spec)
        assert np.array_equal(np.asarray(o), np.asarray(fo[0]))


def test_single_flow_train_ppo_unchanged_by_fleet_refactor():
    """n_flows=1 routes through the untouched single-flow rollout: the PR 2
    train_ppo golden history (pinned in test_unified_env) must also hold
    when the fleet fields sit at their defaults explicitly."""
    from tests.test_unified_env import GOLDEN_HISTORY
    res = train_ppo(_params_read(),
                    PPOConfig(max_episodes=8, n_envs=4, max_steps=5, seed=0,
                              n_flows=1, fairness_coef=0.5))
    np.testing.assert_allclose(res.history, GOLDEN_HISTORY, atol=1e-4)


# ---------------------------------------------------------------------------
# Contention model
# ---------------------------------------------------------------------------

def test_contention_conserves_and_splits_evenly():
    """Equal contending flows split every stage's scheduled cap evenly, and
    the fleet total never exceeds it."""
    p = _params_base()
    flows = always_on(4)
    threads = jnp.full((4, 3), 20.0)
    rates = _fleet_substep_rates(p, constant_table(p.tpt, p.bw, p.duration),
                                 threads, flows, jnp.zeros(()), 10)
    rates = np.asarray(rates)  # (S, F, 3)
    assert (rates.sum(axis=1) <= np.asarray(p.bw) + 1e-6).all()
    np.testing.assert_allclose(
        rates, np.broadcast_to(rates[:, :1, :], rates.shape), atol=1e-6)
    np.testing.assert_allclose(rates.sum(axis=1)[:, 1],
                               np.asarray(p.bw)[1], atol=1e-6)  # saturated


def test_contention_shares_follow_thread_counts():
    """A flow running 3x the threads of its peer gets 3x the share of a
    saturated stage (the live token buckets behave the same way)."""
    p = _params_base()
    flows = always_on(2)
    threads = jnp.asarray([[30.0, 30.0, 30.0], [10.0, 10.0, 10.0]])
    rates = np.asarray(_fleet_substep_rates(
        p, constant_table(p.tpt, p.bw, p.duration), threads, flows,
        jnp.zeros(()), 4))
    np.testing.assert_allclose(rates[:, 0, :], 3.0 * rates[:, 1, :],
                               rtol=1e-5)


def test_inactive_flows_move_nothing_and_free_the_link():
    """Before its arrival a flow has zero effective threads — it moves no
    bytes and does not dilute the active flows' shares."""
    p = _params_base()
    flows = make_flow_schedule([0.0, 100.0], [np.inf, np.inf])
    bufs = jnp.zeros((2, 2))
    threads = jnp.full((2, 3), 10.0)
    bufs2, tps = fleet_interval(p, bufs, threads, 0.0, flows=flows)
    assert np.asarray(tps[1]).max() == 0.0
    assert np.asarray(bufs2[1]).max() == 0.0
    # the sole active flow sees the single-flow rates exactly
    _, tps_solo = sim_interval(p, jnp.zeros(2), threads[0])
    assert np.array_equal(np.asarray(tps[0]), np.asarray(tps_solo))


def test_flows_join_mid_interval_via_substep_activity():
    """Arrival inside an env step is honored at substep granularity: the
    late flow moves bytes only for the active fraction of the interval."""
    p = _params_base()
    flows = make_flow_schedule([0.0, 0.5], [np.inf, np.inf])
    threads = jnp.full((2, 3), 10.0)
    _, tps = fleet_interval(p, jnp.zeros((2, 2)), threads, 0.0, flows=flows)
    assert 0.0 < float(tps[1, 0]) < float(tps[0, 0])


def test_fleet_backends_agree():
    """The pallas substep kernel takes the fleet's (F, S, 3) rate batch
    natively and matches the vmapped jnp scan."""
    p = _params_base()
    flows = make_flow_schedule([0.0, 2.0], [np.inf, 30.0])
    threads = jnp.asarray([[8.0, 4.0, 2.0], [3.0, 9.0, 6.0]])
    bufs_j, tps_j = fleet_interval(p, jnp.zeros((2, 2)), threads, 1.5,
                                   flows=flows, backend="jnp")
    bufs_p, tps_p = fleet_interval(p, jnp.zeros((2, 2)), threads, 1.5,
                                   flows=flows, backend="pallas")
    np.testing.assert_allclose(np.asarray(bufs_j), np.asarray(bufs_p),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(tps_j), np.asarray(tps_p),
                               atol=1e-5)


def test_jain_index_properties():
    assert float(jain_index(jnp.asarray([1.0, 1.0, 1.0, 1.0]))) == \
        pytest.approx(1.0)
    assert float(jain_index(jnp.asarray([1.0, 0.0, 0.0, 0.0]))) == \
        pytest.approx(0.25)
    # inactive flows are excluded, an idle fleet is trivially fair
    act = jnp.asarray([1.0, 1.0, 0.0])
    assert float(jain_index(jnp.asarray([0.5, 0.5, 9.9]), act)) == \
        pytest.approx(1.0)
    assert float(jain_index(jnp.zeros(3))) == pytest.approx(1.0)


def test_fleet_achievable_scales_with_active_population():
    p = _params_base()
    flows = make_flow_schedule([0.0, 10.0], [np.inf, np.inf])
    tab = constant_table(p.tpt, p.bw, p.duration)
    # one active flow: bottleneck = min(50 * 0.15, 1.0) = 1.0 already
    assert float(fleet_achievable(p, tab, flows, 5.0)) == pytest.approx(1.0)
    assert float(fleet_achievable(p, tab, flows, 15.0)) == pytest.approx(1.0)
    none_active = make_flow_schedule([10.0], [20.0])
    assert float(fleet_achievable(p, tab, none_active, 5.0)) == 0.0


# ---------------------------------------------------------------------------
# ObservationSpec fleet dims + arrival schedules
# ---------------------------------------------------------------------------

def test_fleet_obs_spec_dims():
    assert FLEET_OBS.dim == OBS_DIM + CONTEXT_DIM + FLEET_DIM == 16
    assert ObservationSpec(fleet=True).dim == OBS_DIM + FLEET_DIM == 11
    assert DEFAULT_OBS.dim == 8 and CONTEXT_OBS.dim == 13  # unchanged


def test_fleet_observe_cross_flow_features():
    p = _params_base()
    flows = make_flow_schedule([0.0, 0.0, 50.0], [np.inf, np.inf, np.inf])
    st = fleet_reset(p, jax.random.PRNGKey(0), 3, flows=flows)
    obs = np.asarray(fleet_observe(p, st, flows=flows, spec=FLEET_OBS))
    assert obs.shape == (3, 16)
    tps = np.asarray(st.throughputs)
    act = np.asarray([1.0, 1.0, 0.0])
    agg = float((tps[:, 1] * act).sum())
    np.testing.assert_allclose(obs[:, 13], 2.0 / 3.0, atol=1e-6)  # frac
    np.testing.assert_allclose(obs[:, 14], agg / 1.0, atol=1e-6)  # agg util
    np.testing.assert_allclose(obs[:, 15], tps[:, 1] * act / max(agg, 1e-9),
                               atol=1e-6)                          # my share
    # the per-flow prefix is the single-flow context observation
    assert obs[:, :13].shape == (3, 13)


def test_arrival_families_deterministic_and_active():
    from repro.scenarios import ARRIVAL_FAMILIES, arrival_schedule
    for fam in ARRIVAL_FAMILIES:
        a = arrival_schedule(fam, 5, horizon=60.0, seed=9)
        b = arrival_schedule(fam, 5, horizon=60.0, seed=9)
        assert np.array_equal(np.asarray(a.t_start), np.asarray(b.t_start))
        assert np.array_equal(np.asarray(a.t_end), np.asarray(b.t_end))
        assert (np.asarray(a.t_start) <= 60.0).all()
    stag = arrival_schedule("staggered_start", 4, horizon=60.0,
                            spacing_frac=0.25)
    np.testing.assert_allclose(np.asarray(stag.t_start), [0, 15, 30, 45])
    mask = np.asarray(active_at(stag, 20.0))
    np.testing.assert_allclose(mask, [1, 1, 0, 0])
    crowd = arrival_schedule("flash_crowd", 3, horizon=60.0)
    assert float(crowd.t_start[0]) == 0.0
    np.testing.assert_allclose(np.asarray(active_at(crowd, 30.0)), [1, 1, 1])
    np.testing.assert_allclose(np.asarray(active_at(crowd, 55.0)), [1, 0, 0])
    pois = arrival_schedule("poisson_arrivals", 6, horizon=60.0, seed=4)
    assert float(pois.t_start[0]) == 0.0  # anchored


def test_staggered_start_clips_late_flows_into_horizon():
    """Large fleets must not schedule flows past the episode: flow i's
    i*spacing_frac*horizon start is clipped to 0.9*horizon (the
    poisson_arrivals guard), so every flow is active before the end."""
    from repro.scenarios import arrival_schedule
    stag = arrival_schedule("staggered_start", 12, horizon=60.0)
    starts = np.asarray(stag.t_start)
    assert (starts <= 0.9 * 60.0 + 1e-6).all(), starts
    # everyone is active by the tail of the episode
    np.testing.assert_allclose(np.asarray(active_at(stag, 59.0)),
                               np.ones(12))
    # the early, in-horizon arrivals are untouched by the clip
    np.testing.assert_allclose(starts[:6], np.arange(6) * 0.15 * 60.0)


def test_sample_fleet_batch_shapes_and_determinism():
    from repro.scenarios import sample_fleet_batch
    specs, tables, flows, objs = sample_fleet_batch(6, 4, seed=3,
                                                    horizon=30.0)
    assert tables.tpt.shape[0] == 6 and flows.t_start.shape == (6, 4)
    assert objs.weight.shape == (6, 4)
    assert np.array_equal(np.asarray(objs.weight), np.ones((6, 4)))
    _, t2, f2, _ = sample_fleet_batch(6, 4, seed=3, horizon=30.0)
    assert np.array_equal(np.asarray(flows.t_start), np.asarray(f2.t_start))
    assert np.array_equal(np.asarray(tables.tpt), np.asarray(t2.tpt))


# ---------------------------------------------------------------------------
# Fleet training
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["mlp", "stacked", "gru"])
def test_fleet_training_smoke_all_policies(policy):
    """One shared policy vmapped over a 3-flow fleet trains under every
    temporal stack (the existing per-flow policies, unchanged)."""
    p = _params_base()
    cfg = PPOConfig(max_episodes=4, n_envs=2, max_steps=4, seed=0, n_flows=3,
                    fairness_coef=0.5, obs_spec=FLEET_OBS, policy=policy,
                    history=2)
    res = train_ppo(p, cfg)
    assert res.episodes == 4
    assert np.isfinite(res.history).all()


def test_fleet_training_with_arrival_randomization():
    from repro.scenarios import sample_fleet_batch
    p = _params_base()
    _, tables, flows, _ = sample_fleet_batch(2, 3, seed=0, horizon=30.0)
    cfg = PPOConfig(max_episodes=4, n_envs=2, max_steps=4, seed=0, n_flows=3,
                    fairness_coef=0.5, obs_spec=FLEET_OBS)
    res = train_ppo(p, cfg, tables=tables, flows=flows)
    assert np.isfinite(res.history).all()
    mean, _ = nets.policy_apply(res.params["policy"], jnp.zeros((3, 16)))
    assert mean.shape == (3, 3)


def test_fairness_coef_rewards_even_splits():
    """With contending flows, the Jain term pays out: an even fleet scores
    a strictly higher reward under fairness_coef > 0 than the same fleet
    with the bonus off."""
    p = _params_base()
    st = fleet_reset(p, jax.random.PRNGKey(1), 2)
    a = jnp.full((2, 3), 10.0)
    _, _, r0 = fleet_step(p, st, a, fairness_coef=0.0)
    _, _, r1 = fleet_step(p, st, a, fairness_coef=0.5)
    assert float(r1) == pytest.approx(float(r0) + 0.5, abs=1e-5)


def test_train_ppo_vectorized_removed():
    """The redundant wrapper completed its deprecation:
    train_ppo(..., PPOConfig(n_envs=...)) is the only vectorized path."""
    import repro.core as core
    import repro.core.ppo as ppo
    assert not hasattr(ppo, "train_ppo_vectorized")
    assert not hasattr(core, "train_ppo_vectorized")


# ---------------------------------------------------------------------------
# Live twin: FleetPolicy / FleetController parity with the sim
# ---------------------------------------------------------------------------

def test_fleet_controller_is_live_twin_of_fleet_observe():
    """The FleetController builds the exact (F, 16) matrix fleet_observe
    derives — per-flow frames AND cross-flow features — from consecutive
    observe() dicts, and the shared policy then emits identical actions."""
    p = _params_base()
    flows = always_on(3)
    st = fleet_reset(p, jax.random.PRNGKey(5), 3, flows=flows)
    acts = jnp.asarray([[12.0, 9.0, 7.0], [4.0, 16.0, 3.0],
                        [8.0, 8.0, 8.0]])
    st2, obs_sim, _ = fleet_step(p, st, acts, flows=flows, spec=FLEET_OBS)

    pol = nets.policy_init(jax.random.PRNGKey(0), obs_dim=FLEET_OBS.dim)
    ctrl = FleetController(pol, n_flows=3, n_max=float(p.n_max), bw_ref=1.0,
                           obs_spec=FLEET_OBS, deterministic=True)

    def dicts(s):
        return [_obs_dict(p, s.threads[f], s.throughputs[f],
                          np.asarray(s.buffers[f])) for f in range(3)]

    ctrl.frames(dicts(st))   # primes per-flow prev throughputs
    frames = ctrl.frames(dicts(st2))
    np.testing.assert_allclose(frames, np.asarray(obs_sim), atol=1e-5)

    # frames() advances the per-flow prev-throughput state, so the action
    # check runs on a fresh controller stepped once per observation epoch
    ctrl2 = FleetController(pol, n_flows=3, n_max=float(p.n_max), bw_ref=1.0,
                            obs_spec=FLEET_OBS, deterministic=True)
    ctrl2.step(dicts(st))    # primes per-flow prev throughputs
    live_actions = np.asarray(ctrl2.step(dicts(st2)))
    fp = FleetPolicy(pol, n_max=float(p.n_max), obs_spec=FLEET_OBS,
                     deterministic=True)
    sim_actions = fp.act(np.asarray(obs_sim))
    np.testing.assert_array_equal(sim_actions, live_actions)


def test_fleet_policy_maintains_history_and_carry():
    pol = nets.policy_init(jax.random.PRNGKey(0), obs_dim=16 * 2)
    fp = FleetPolicy(pol, obs_spec=ObservationSpec(context=True, fleet=True,
                                                   history=2))
    a1 = fp.act(np.ones((3, 16), np.float32))
    assert a1.shape == (3, 3) and fp._hist.shape == (3, 2, 16)
    fp.reset()
    assert fp._hist is None
    g = nets.rnn_policy_init(jax.random.PRNGKey(1), obs_dim=16)
    fg = FleetPolicy(g, obs_spec=FLEET_OBS, policy="gru")
    fg.act(np.ones((4, 16), np.float32))
    assert fg._carry.shape == (4, 64)


def test_fleet_eval_shared_policy_beats_static_on_arrivals():
    """A tiny-budget shared fleet policy already beats the per-flow static
    baseline on aggregate utilization under staggered arrivals (the cheap
    in-tier-1 version of the bench_fleet acceptance bar), at Jain >= 0.9."""
    from repro.core import GlobusController
    from repro.scenarios import (ScenarioSpec, arrival_schedule,
                                 run_fleet_in_dynamic_sim, sample_fleet_batch)
    p = _params_base()
    _, tables, flows_b, _ = sample_fleet_batch(4, 3, seed=1, horizon=30.0)
    cfg = PPOConfig(max_episodes=24, n_envs=4, max_steps=8, seed=1,
                    n_flows=3, fairness_coef=0.5, obs_spec=FLEET_OBS,
                    action_scale=12.5, param_selection="batch_mean")
    res = train_ppo(p, cfg, tables=tables, flows=flows_b)
    fp = FleetPolicy(res.params["policy"], n_max=50, obs_spec=FLEET_OBS)
    spec = ScenarioSpec(family="static", seed=11, horizon=30.0)
    flows = arrival_schedule("staggered_start", 3, horizon=30.0)
    ours = run_fleet_in_dynamic_sim(spec, flows, p, fp, label="fleet",
                                    arrival="staggered_start")
    static = run_fleet_in_dynamic_sim(
        spec, flows, p, [GlobusController() for _ in range(3)],
        label="static", arrival="staggered_start")
    assert ours.utilization > static.utilization
    assert ours.jain >= 0.9


def test_fleet_controller_shares_one_bw_reference():
    """Without an explicit bw_ref, every flow's frame must normalize by ONE
    fleet-wide running max — the sim divides all flows by the same schedule
    peak, so a flow that only ever ran under contention must not read its
    throughputs ~2x larger than a flow that once held the whole link."""
    p = _params_base()
    pol = nets.policy_init(jax.random.PRNGKey(0), obs_dim=FLEET_OBS.dim)
    ctrl = FleetController(pol, n_flows=2, n_max=float(p.n_max),
                           obs_spec=FLEET_OBS, deterministic=True)
    obs = [_obs_dict(p, [4, 4, 4], [1.0, 0.9, 0.8], np.zeros(2)),
           _obs_dict(p, [4, 4, 4], [0.5, 0.45, 0.4], np.zeros(2))]
    frames = ctrl.frames(obs)
    # dims 3:6 are throughputs / bw — both rows over the SAME reference
    # (the fleet max 1.0), not each flow's own running max
    np.testing.assert_allclose(frames[0, 3:6], [1.0, 0.9, 0.8], atol=1e-6)
    np.testing.assert_allclose(frames[1, 3:6], [0.5, 0.45, 0.4], atol=1e-6)
