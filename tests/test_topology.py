"""Multi-link topology core: the E=1 embedding must be BIT-identical to
the PR 5 fleet path (atol=0), a flow's rate must be the min over its
links, the per-link solve must be work-conserving under caps, routing
must move rates at route-bin boundaries, TOPOLOGY_OBS must extend the
fleet frame with the topology block, the live TopologyController must
emit exactly the sim's feature rows (live/sim parity), training must run
over topologies for all three temporal policies, and the live MultiLink
must enforce min-over-path and all-or-refund token acquisition."""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.controller import FleetController, TopologyController
from repro.core.fleet import (always_on, make_flow_schedule,
                              make_flow_objective, default_objectives,
                              fleet_reset, fleet_step, fleet_observe,
                              _fleet_substep_rates)
from repro.core.ppo import PPOConfig, train_ppo
from repro.core.schedule import make_table, constant_table, peak_bw
from repro.core.simulator import (make_env_params, FLEET_OBS, TOPOLOGY_OBS,
                                  ObservationSpec, OBS_DIM, CONTEXT_DIM,
                                  FLEET_DIM, TOPO_DIM)
from repro.core.topology import (LinkGraph, make_link_graph,
                                 single_link_graph, make_path_spec,
                                 all_links_path, stack_topologies,
                                 routes_at, graph_peak_bw, link_peak_bw,
                                 topology_reset, topology_step,
                                 topology_observe, topology_features,
                                 topology_achievable,
                                 _topology_substep_rates)
from repro.scenarios import TopologySpec, sample_topology_batch

pytestmark = pytest.mark.topology

SUBSTEPS = 6


def _params():
    return make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _sched_table():
    return make_table(np.asarray([[0.2, 0.05, 0.2], [0.1, 0.02, 0.1]],
                                 np.float32),
                      np.full((2, 3), 2.0, np.float32), bin_seconds=2.0)


def _obs_dict(threads, tps, p):
    return {"threads": list(np.asarray(threads, float)),
            "throughputs": list(np.asarray(tps, float)),
            "sender_free": float(p.cap[0]),
            "receiver_free": float(p.cap[1]),
            "sender_capacity": float(p.cap[0]),
            "receiver_capacity": float(p.cap[1])}


# ---------------------------------------------------------------------------
# E=1 bit-identity (atol=0) — the acceptance pin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("objectives", ["none", "floors"])
def test_e1_rates_bit_identical_to_fleet(objectives):
    """The single-link topology solve IS the fleet solve: every array op
    added for the multi-link case (path mask, cap water-fill, min-combine)
    must be an exact float no-op at E=1 with caps at inf."""
    p = _params()
    tab = _sched_table()
    rng = np.random.default_rng(0)
    for trial in range(8):
        F = int(rng.integers(1, 5))
        threads = jnp.asarray(rng.integers(1, 30, (F, 3)), jnp.float32)
        flows = make_flow_schedule(rng.uniform(0, 2, F),
                                   rng.uniform(2, 4, F))
        objs = None
        if objectives == "floors":
            objs = make_flow_objective(
                rate_floor=rng.uniform(0, 0.4, F))
        t0 = jnp.asarray(rng.uniform(0, 3), jnp.float32)
        want = _fleet_substep_rates(p, tab, threads, flows, t0, SUBSTEPS,
                                    objs)
        got = _topology_substep_rates(p, single_link_graph(tab),
                                      all_links_path(F, 1), threads, flows,
                                      t0, SUBSTEPS, objs)
        assert np.array_equal(np.asarray(want), np.asarray(got)), trial


def test_e1_chain_bit_identical_to_fleet():
    """reset -> steps -> observe through the topology entry points on a
    single-link graph reproduces the fleet chain exactly (same key stream,
    same reward float, same FLEET_OBS rows)."""
    p = _params()
    tab = _sched_table()
    graph, paths = single_link_graph(tab), all_links_path(3, 1)
    flows = make_flow_schedule([0.0, 1.0, 2.0], [9.0, 9.0, 9.0])
    key = jax.random.PRNGKey(3)
    fst = fleet_reset(p, key, 3, flows=flows, table=tab, substeps=SUBSTEPS)
    tst = topology_reset(p, key, 3, graph=graph, paths=paths, flows=flows,
                         substeps=SUBSTEPS)
    for a, b in zip(jax.tree_util.tree_leaves(fst),
                    jax.tree_util.tree_leaves(tst)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    acts = jax.random.uniform(jax.random.PRNGKey(4), (4, 3, 3)) * 30
    for i in range(4):
        fst, fobs, frew = fleet_step(p, fst, acts[i], flows=flows,
                                     table=tab, substeps=SUBSTEPS,
                                     spec=FLEET_OBS, fairness_coef=0.5)
        tst, tobs, trew = topology_step(p, tst, acts[i], graph=graph,
                                        paths=paths, flows=flows,
                                        substeps=SUBSTEPS, spec=FLEET_OBS,
                                        fairness_coef=0.5)
        assert float(frew) == float(trew)
        assert np.array_equal(np.asarray(fobs), np.asarray(tobs))
    want = fleet_observe(p, fst, flows=flows, table=tab, spec=FLEET_OBS)
    got = topology_observe(p, tst, flows=flows, graph=graph, paths=paths,
                           spec=FLEET_OBS)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    assert float(graph_peak_bw(graph)) == float(peak_bw(tab))


# ---------------------------------------------------------------------------
# The multi-link solve
# ---------------------------------------------------------------------------

def test_rate_is_min_over_path_links():
    """A lone flow crossing a fast and a slow link runs at the slow link's
    rate; a flow crossing only the fast link keeps the fast rate."""
    p = _params()
    graph = make_link_graph(
        tpt=np.broadcast_to([[10.0, 10.0, 10.0]], (2, 1, 3))[..., :],
        bw=np.asarray([[[4.0, 4.0, 4.0]], [[1.0, 1.0, 1.0]]]))
    both = make_path_spec([[1.0, 1.0]])
    fast = make_path_spec([[1.0, 0.0]])
    threads = jnp.ones((1, 3))
    flows = always_on(1)
    r_both = _topology_substep_rates(p, graph, both, threads, flows, 0.0, 1)
    r_fast = _topology_substep_rates(p, graph, fast, threads, flows, 0.0, 1)
    assert np.allclose(np.asarray(r_both)[0, 0], 1.0)
    assert np.allclose(np.asarray(r_fast)[0, 0], 4.0)


def _four_sites():
    """The four-site DTN deployment of the benchmark
    (``bench/configs/petascale_dtn_4site.json``): its configuration, the
    program's graph and static routes built from it, and the benchmark's
    plain reference of the same solve (``bench/ref/topology.py``)."""
    import importlib
    import json
    import sys
    from pathlib import Path
    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    ref = importlib.import_module("ref.topology")
    config = json.loads((bench / "configs" / "petascale_dtn_4site.json")
                        .read_text())
    e, g = config["env"], config["graph"]
    links = g["links"]
    onpath = [[float(name in f["links"]) for name in links]
              for f in g["flows"]]
    shape = (len(links), 1, 3)
    graph = make_link_graph(np.broadcast_to(e["tpt"], shape),
                            np.broadcast_to(e["bw"], shape))
    params = make_env_params(tpt=e["tpt"], bw=e["bw"], cap=e["cap"],
                             n_max=e["n_max"])
    return config, params, graph, make_path_spec(onpath), ref


def test_four_site_rates_match_a_hand_worked_case_and_the_reference():
    """ALCF's three outgoing flows (30, 10 and 10 threads) split its
    100 Gbit/s egress 3:1:1, so 60, 20 and 20 Gbit/s of network stage
    (each could move 2.5 Gbit/s a thread). ALCF->NCSA is held by that
    egress share, 60 (its NCSA ingress share is 30/34 of 100 and its
    threads move 75); ALCF->NERSC by its NERSC ingress share, 10/52 of
    100 = 19.23, below its egress 20, since NCSA->NERSC brings 40 threads
    there. The program's solve and the benchmark's reference agree on
    every flow and stage."""
    config, params, graph, paths, ref = _four_sites()
    pairs = [(f["from"], f["to"]) for f in config["graph"]["flows"]]
    n = {("ALCF", "NCSA"): 30.0, ("ALCF", "NERSC"): 10.0,
         ("ALCF", "OLCF"): 10.0, ("NCSA", "NERSC"): 40.0}
    threads = jnp.asarray([[n.get(pr, 2.0)] * 3 for pr in pairs])
    got = np.asarray(_topology_substep_rates(
        params, graph, paths, threads, always_on(len(pairs)), 0.0, 1))[0]
    net = dict(zip(pairs, got[:, 1]))
    want = {("ALCF", "NCSA"): 60.0, ("ALCF", "NERSC"): 100.0 * 10 / 52,
            ("ALCF", "OLCF"): 20.0, ("NCSA", "NERSC"): 100.0 * 40 / 52,
            ("NCSA", "ALCF"): 100.0 * 2 / 44, ("OLCF", "NERSC"): 100.0 * 2 / 52,
            ("NERSC", "ALCF"): 5.0}
    for pr, v in want.items():
        assert net[pr] == pytest.approx(v, rel=1e-6), pr
    world = ref.world_of(config)
    np.testing.assert_allclose(np.asarray(ref.rates(threads, world))[0], got,
                               rtol=1e-6)


def test_the_topology_episode_program_names_its_four_scopes():
    """The solve, the integration, the observation and the reward of the
    topology rollout carry their named scopes in the episode program's op
    metadata (``op_name``, as the compiled program holds it)."""
    from repro.core import ppo
    from repro.core.topology import Topology
    _, params, graph, paths, _ = _four_sites()
    cfg = PPOConfig(max_episodes=2, n_envs=2, n_flows=paths.n_flows,
                    max_steps=2, substeps=4, obs_spec=TOPOLOGY_OBS)
    fn = ppo._make_episode_fn(params, cfg, randomize_t0=True, topology=True)
    state = ppo.init_agent(jax.random.PRNGKey(0), cfg)
    topo = stack_topologies([Topology(graph, paths)] * cfg.n_envs)
    flows = ppo._broadcast_table(always_on(cfg.n_flows), cfg.n_envs)
    text = fn.lower(state, None, flows, None, topo,
                    jax.random.PRNGKey(1)).compile().as_text()
    for scope in ("topology.solve", "topology.integrate",
                  "topology.observe", "topology.reward"):
        assert re.search(r'op_name="jit\(episode\)/rollout/[^"]*/'
                         + re.escape(scope) + r'[/"]', text), scope


def test_work_conserving_under_caps():
    """One capped flow + two uncapped on a saturated link: the capped
    flow's unused share spills to the others and the link still moves its
    full capacity — the fleet solve strands that share (the PR 5 open
    item this subsystem closes)."""
    p = _params()
    tab = constant_table([10.0, 10.0, 10.0], [1.0, 1.0, 1.0], 1.0)
    threads = jnp.full((3, 3), 10.0)
    flows = always_on(3)
    objs = make_flow_objective(rate_cap=[0.05, np.inf, np.inf])
    topo = np.asarray(_topology_substep_rates(
        p, single_link_graph(tab), all_links_path(3, 1), threads, flows,
        0.0, 1, objs))[0]
    assert np.allclose(topo.sum(axis=0), 1.0, atol=1e-5)  # full capacity
    assert np.allclose(topo[0], 0.05, atol=1e-6)          # cap still binds
    fleet = np.asarray(_fleet_substep_rates(p, tab, threads, flows, 0.0, 1,
                                            objs))[0]
    assert fleet.sum(axis=0).max() < 0.75  # the old solve strands ~0.3


def test_empty_path_and_inactive_flows_move_nothing():
    p = _params()
    graph = make_link_graph(tpt=np.full((2, 1, 3), 10.0),
                            bw=np.full((2, 1, 3), 1.0))
    paths = make_path_spec([[1.0, 0.0], [0.0, 0.0]])  # flow 1 routed nowhere
    flows = make_flow_schedule([0.0, 0.0], [10.0, 10.0])
    rates = np.asarray(_topology_substep_rates(
        p, graph, paths, jnp.full((2, 3), 5.0), flows, 0.0, 2))
    assert (rates[:, 1] == 0.0).all()
    assert (rates[:, 0] > 0.0).all()
    late = np.asarray(_topology_substep_rates(  # both flows ended
        p, graph, paths, jnp.full((2, 3), 5.0), flows, 50.0, 2))
    assert (late == 0.0).all()


def test_failover_routing_moves_rates_at_route_bin():
    """A 2-row PathSpec re-routes mid-transfer: before the cut the flow
    rides link 0, after it link 1 — and the dead link 0 stops binding."""
    p = _params()
    tpt = np.full((2, 4, 3), 10.0)
    bw = np.stack([np.asarray([[2.0] * 3] * 2 + [[0.02] * 3] * 2),   # dies
                   np.full((4, 3), 1.0)])                            # standby
    graph = make_link_graph(tpt, bw, bin_seconds=5.0)
    paths = make_path_spec([[[1.0, 0.0]], [[0.0, 1.0]]], bin_seconds=10.0)
    assert np.array_equal(np.asarray(routes_at(paths, 3.0)), [[1.0, 0.0]])
    assert np.array_equal(np.asarray(routes_at(paths, 12.0)), [[0.0, 1.0]])
    threads = jnp.full((1, 3), 10.0)
    early = np.asarray(_topology_substep_rates(
        p, graph, paths, threads, always_on(1), 0.0, 1))
    post_cut_no_move = np.asarray(_topology_substep_rates(
        p, graph, paths, threads, always_on(1), 19.0, 1))
    assert np.allclose(early[0, 0], 2.0)
    # t=19 is past the cut (bin 2 of the graph) AND past the route bin:
    # the flow rides the standby at 1.0 instead of the dead primary at 0.02
    assert np.allclose(post_cut_no_move[0, 0], 1.0)


def test_achievable_scales_with_routes():
    p = _params()
    graph = make_link_graph(tpt=np.full((2, 1, 3), 10.0),
                            bw=np.full((2, 1, 3), 1.0))
    flows = always_on(2)
    split = make_path_spec([[1.0, 0.0], [0.0, 1.0]])  # disjoint: 2 links
    shared = make_path_spec([[1.0, 0.0], [1.0, 0.0]])  # both on link 0
    a_split = float(topology_achievable(p, graph, split, flows, 0.0))
    a_shared = float(topology_achievable(p, graph, shared, flows, 0.0))
    assert np.isclose(a_split, 2.0, atol=1e-5)
    assert np.isclose(a_shared, 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# Observation + controller parity
# ---------------------------------------------------------------------------

def test_topology_obs_dims():
    assert TOPO_DIM == 3
    assert TOPOLOGY_OBS.frame_dim == (OBS_DIM + CONTEXT_DIM + FLEET_DIM
                                      + TOPO_DIM) == 19
    assert ObservationSpec(topology=True).frame_dim == OBS_DIM + TOPO_DIM
    assert FLEET_OBS.frame_dim == 16  # unchanged


def test_topology_observe_appends_feature_block():
    p = _params()
    graph = make_link_graph(tpt=np.full((2, 1, 3), 10.0),
                            bw=np.full((2, 1, 3), 1.0))
    paths = make_path_spec([[1.0, 1.0], [1.0, 0.0]])
    flows = always_on(2)
    st = topology_reset(p, jax.random.PRNGKey(0), 2, graph=graph,
                        paths=paths, flows=flows, substeps=SUBSTEPS)
    obs = np.asarray(topology_observe(p, st, flows=flows, graph=graph,
                                      paths=paths, spec=TOPOLOGY_OBS))
    assert obs.shape == (2, 19)
    base = np.asarray(topology_observe(p, st, flows=flows, graph=graph,
                                       paths=paths, spec=FLEET_OBS))
    assert np.array_equal(obs[:, :16], base)
    want = np.asarray(topology_features(
        routes_at(paths, st.t), st.throughputs[:, 1], [1.0, 1.0],
        link_peak_bw(graph)))
    assert np.array_equal(obs[:, 16:], want)
    assert np.allclose(obs[:, 17], [1.0, 0.5])  # path length / E


def test_topology_controller_parity_with_sim_features():
    """The live TopologyController appends literally the sim's
    topology_features rows on top of the FleetController frame."""
    p = _params()
    onpath = np.asarray([[1.0, 1.0], [0.0, 1.0]])
    link_bw = [1.0, 2.0]
    kw = dict(n_flows=2, n_max=50, bw_ref=2.0, obs_spec=TOPOLOGY_OBS)
    ctrl = TopologyController(None, paths=onpath, link_bw_ref=link_bw, **kw)
    base_ctrl = FleetController(None, **{**kw, "obs_spec": FLEET_OBS})
    obs_list = [_obs_dict([4, 4, 4], [0.5, 0.4, 0.5], p),
                _obs_dict([2, 2, 2], [0.3, 0.2, 0.3], p)]
    frames = ctrl.frames(obs_list, active=[1.0, 1.0])
    assert frames.shape == (2, 19)
    base = base_ctrl.frames(obs_list, active=[1.0, 1.0])
    assert np.array_equal(frames[:, :16], base)
    want = np.asarray(topology_features(onpath, [0.4, 0.2], [1.0, 1.0],
                                        link_bw), np.float32)
    assert np.allclose(frames[:, 16:], want, atol=1e-7)


def test_topology_controller_routes_follow_route_bins():
    paths = make_path_spec([[[1.0, 0.0]], [[0.0, 1.0]]], bin_seconds=10.0)
    ctrl = TopologyController(None, paths=paths, link_bw_ref=[1.0, 1.0],
                              n_flows=1, obs_spec=TOPOLOGY_OBS)
    assert np.array_equal(ctrl.routes(0.0), [[1.0, 0.0]])
    assert np.array_equal(ctrl.routes(25.0), [[0.0, 1.0]])
    with pytest.raises(ValueError):
        TopologyController(None, paths=np.ones((3, 2)), link_bw_ref=[1, 1],
                           n_flows=2, obs_spec=TOPOLOGY_OBS)


# ---------------------------------------------------------------------------
# Training over topologies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["mlp", "stacked", "gru"])
def test_train_ppo_topology_smoke(policy):
    p = _params()
    _, topo, flows, _ = sample_topology_batch(
        4, 2, n_links=2, seed=0, horizon=30.0,
        base_tpt=(0.2, 0.15, 0.2), base_bw=(1.0, 1.0, 1.0))
    cfg = PPOConfig(max_episodes=8, n_envs=4, n_flows=2, max_steps=4,
                    obs_spec=TOPOLOGY_OBS, policy=policy, log_every=0,
                    fairness_coef=0.5)
    res = train_ppo(p, cfg, topology=topo, flows=flows)
    assert res.episodes == 8
    assert np.isfinite(res.history).all()


def test_train_ppo_resample_topology():
    p = _params()

    def draw(rnd):
        return sample_topology_batch(
            4, 2, n_links=2, seed=rnd, horizon=30.0,
            base_tpt=(0.2, 0.15, 0.2), base_bw=(1.0, 1.0, 1.0))[1]

    cfg = PPOConfig(max_episodes=12, n_envs=4, n_flows=2, max_steps=4,
                    obs_spec=TOPOLOGY_OBS, log_every=0)
    res = train_ppo(p, cfg, resample_topology=draw)
    assert res.episodes == 12
    assert np.isfinite(res.history).all()


# ---------------------------------------------------------------------------
# Live MultiLink
# ---------------------------------------------------------------------------

def test_pathgate_all_or_refund():
    """A grant on the first pool must be refunded when a later pool
    refuses — otherwise a blocked path burns the shared link's tokens."""
    from repro.transfer import PathGate, StageThrottle
    a, b = StageThrottle(1000), StageThrottle(1000)
    b.set_rates(aggregate_bps=0)  # outage: b refuses everything
    gate = PathGate([a, b])
    assert gate.try_acquire(600) is None
    assert a.try_acquire(600) is not None  # a's tokens were refunded
    a2 = StageThrottle(1000)
    gate2 = PathGate([a2, StageThrottle()])
    assert gate2.try_acquire(600) is not None  # uncapped pool grants free
    assert gate2.try_acquire(600) is None      # a2 drained for real
    assert gate2.rates() == (1000, None)
    gate2.set_pools([a2])
    assert gate2.pools() == [a2]


def test_multilink_attach_reroute_bookkeeping():
    from repro.transfer import MultiLink, SyntheticSource, NullSink
    net = MultiLink(3, aggregate_bps=[(1000,) * 3, (2000,) * 3,
                                      (3000,) * 3])
    assert net.n_links == 3
    e = net.attach(SyntheticSource(1 << 16, chunk_bytes=1 << 12), NullSink(),
                   path=[0, 2], initial_concurrency=(1, 1, 1), n_max=2)
    assert net.path_of(e) == (0, 2)
    assert net.onpath() == [[1.0, 0.0, 1.0]]
    # the engine's gates hold exactly the path links' pools, in order
    assert e.throttles[1].pools() == [net.links[0][1], net.links[2][1]]
    net.reroute(e, [1])
    assert net.path_of(e) == (1,)
    assert e.throttles[0].pools() == [net.links[1][0]]
    assert net.link(1).throttles == list(net.links[1])
    with pytest.raises(ValueError):
        net.attach(SyntheticSource(1 << 12), NullSink(), path=[])
    with pytest.raises(ValueError):
        net.attach(SyntheticSource(1 << 12), NullSink(), path=[3])
    with pytest.raises(ValueError):
        net.reroute(e, [0, 0])
    net.close()


@pytest.mark.slow
def test_multilink_live_failover_replay():
    """Live end-to-end: a flow over [primary, shared] parks when the
    primary dies, a reroute to the standby unparks it, and a flow sharing
    only the healthy link keeps moving throughout (the refund rule)."""
    import time
    from repro.transfer import MultiLink, SyntheticSource, NullSink
    MB = 1 << 20
    net = MultiLink(3, aggregate_bps=4 * MB)
    ea = net.attach(SyntheticSource(64 * MB, chunk_bytes=64 << 10),
                    NullSink(), path=[0, 1],
                    initial_concurrency=(4, 4, 4), n_max=8)
    eb = net.attach(SyntheticSource(64 * MB, chunk_bytes=64 << 10),
                    NullSink(), path=[1], initial_concurrency=(4, 4, 4),
                    n_max=8)
    time.sleep(1.0)
    for t in net.links[0]:  # primary link outage
        t.set_rates(aggregate_bps=0)
    time.sleep(1.0)
    a0, b0 = ea.bytes_written(), eb.bytes_written()
    time.sleep(1.5)
    assert ea.bytes_written() - a0 < 1 * MB      # A parked at the outage
    assert eb.bytes_written() - b0 > 3 * MB      # B unharmed (refunds)
    net.reroute(ea, [2, 1])                      # fail over to the standby
    time.sleep(2.0)
    assert ea.bytes_written() - a0 > 2 * MB      # A recovered
    net.close()
