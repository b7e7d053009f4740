"""Property-based fleet invariants (hypothesis): the contention model must
CONSERVE capacity for any fleet/schedule/objective draw, inactive flows must
deliver exactly nothing, the F=1 fleet must equal the single-flow env
bit-for-bit across randomized parameters (not just the fixed goldens), the
Jain index must live in (0, 1], and the shared policy must be equivariant
under any permutation of the flows. These are the invariants the fleet
goldens pin by example — here they are pinned for 200+ random draws each
(the fleet invariant gate; auto-skips where hypothesis is absent)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")  # not baked into every CI image
from hypothesis import given, settings, strategies as st

from repro.core import networks as nets
from repro.core.fleet import (FleetState, make_flow_schedule, always_on,
                              make_flow_objective, active_at, fleet_reset,
                              fleet_step, fleet_observe, fleet_interval,
                              jain_index, _fleet_substep_rates, flow_bucket)
from repro.core.schedule import make_table
from repro.core.simulator import (make_env_params, env_reset, env_step,
                                  FLEET_OBS)
from repro.core.topology import (single_link_graph, all_links_path,
                                 make_link_graph, make_path_spec,
                                 topology_interval,
                                 _topology_substep_rates)

# small, fixed shape pools keep the jitted paths to a handful of compiles
# across all 200+ examples (values are traced, shapes are static)
SUBSTEPS = 6
rate_st = st.floats(0.02, 0.5)
bw_st = st.floats(0.1, 2.0)
n_flows_st = st.integers(1, 3)


@st.composite
def fleet_world(draw, n_flows=None):
    """A random (params, table, flows, threads) fleet configuration with a
    2-bin schedule and per-flow activity windows around the simulated
    interval [0, 1)."""
    F = n_flows if n_flows is not None else draw(n_flows_st)
    params = make_env_params(
        tpt=[draw(rate_st) for _ in range(3)],
        bw=[draw(bw_st) for _ in range(3)],
        cap=[draw(st.floats(0.5, 3.0))] * 2, n_max=50)
    table = make_table(
        np.asarray([[draw(rate_st) for _ in range(3)] for _ in range(2)],
                   np.float32),
        np.asarray([[draw(bw_st) for _ in range(3)] for _ in range(2)],
                   np.float32), bin_seconds=0.5)
    t_start = [draw(st.floats(0.0, 1.5)) for _ in range(F)]
    t_end = [s + draw(st.floats(0.1, 2.0)) for s in t_start]
    flows = make_flow_schedule(t_start, t_end)
    threads = jnp.asarray(
        [[draw(st.integers(1, 30)) for _ in range(3)] for _ in range(F)],
        jnp.float32)
    return params, table, flows, threads


@st.composite
def objectives_for(draw, n_flows):
    """Random floors/caps/weights (possibly oversubscribed floors — the
    model must scale them, never over-commit)."""
    floors = [draw(st.floats(0.0, 1.5)) for _ in range(n_flows)]
    caps = [draw(st.one_of(st.just(np.inf), st.floats(0.05, 1.5)))
            for _ in range(n_flows)]
    weights = [draw(st.sampled_from([1.0, 2.0, 4.0]))
               for _ in range(n_flows)]
    return make_flow_objective(weight=weights, rate_floor=floors,
                               rate_cap=caps)


# ---------------------------------------------------------------------------
# Conservation: the fleet never outruns the scheduled capacity
# ---------------------------------------------------------------------------

@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_substep_rates_conserve_scheduled_bandwidth(data):
    """At every substep, the per-stage sum of per-flow rates is bounded by
    that substep's scheduled aggregate bandwidth — for any fleet size,
    schedule, activity pattern, and (floored/capped/oversubscribed)
    objectives."""
    params, table, flows, threads = data.draw(fleet_world())
    F = threads.shape[0]
    obj = data.draw(st.one_of(st.none(), objectives_for(F)))
    rates = np.asarray(_fleet_substep_rates(
        params, table, threads, flows, jnp.zeros(()), SUBSTEPS, obj))
    assert rates.shape == (SUBSTEPS, F, 3)
    assert (rates >= 0.0).all()
    dt = float(params.duration) / SUBSTEPS
    ts = dt * np.arange(SUBSTEPS)
    idx = np.clip((ts / float(np.asarray(table.bin_seconds))).astype(int),
                  0, table.bw.shape[0] - 1)
    bw = np.asarray(table.bw)[idx]                      # (S, 3)
    assert (rates.sum(axis=1) <= bw * (1 + 1e-5) + 1e-6).all(), \
        (rates.sum(axis=1), bw)


@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_inactive_flows_deliver_exactly_zero(data):
    """A flow whose window misses the simulated interval entirely moves not
    one byte: zero throughput, zero buffer occupancy — exactly, not
    approximately."""
    params, table, _, threads = data.draw(fleet_world())
    F = threads.shape[0]
    # flow 0 active, the rest strictly after the interval [0, duration)
    t_start = [0.0] + [float(params.duration) + 0.5] * (F - 1)
    flows = make_flow_schedule(t_start, [np.inf] * F)
    bufs, tps = fleet_interval(params, jnp.zeros((F, 2)), threads, 0.0,
                               flows=flows, table=table, substeps=SUBSTEPS)
    if F > 1:
        assert np.asarray(tps[1:]).max() == 0.0
        assert np.asarray(bufs[1:]).max() == 0.0
    assert np.isfinite(np.asarray(tps)).all()


# ---------------------------------------------------------------------------
# Topology solve: E=1 embedding is the fleet solve; caps never strand
# capacity
# ---------------------------------------------------------------------------

@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_topology_e1_rates_equal_fleet_rates_bitwise(data):
    """For ANY fleet draw (optionally with floors — caps stay at inf, where
    the water-fill must be an exact float no-op), the topology solve on the
    single-link graph equals `_fleet_substep_rates` with atol=0."""
    params, table, flows, threads = data.draw(fleet_world())
    F = threads.shape[0]
    obj = data.draw(st.one_of(st.none(), st.builds(
        make_flow_objective,
        rate_floor=st.lists(st.floats(0.0, 1.5), min_size=F, max_size=F),
        weight=st.lists(st.sampled_from([1.0, 2.0, 4.0]),
                        min_size=F, max_size=F))))
    t0 = jnp.asarray(data.draw(st.floats(0.0, 2.0)), jnp.float32)
    want = np.asarray(_fleet_substep_rates(params, table, threads, flows,
                                           t0, SUBSTEPS, obj))
    got = np.asarray(_topology_substep_rates(
        params, single_link_graph(table), all_links_path(F, 1), threads,
        flows, t0, SUBSTEPS, obj))
    assert np.array_equal(want, got)


@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_topology_caps_strand_no_capacity(data):
    """Work conservation, the property the fleet solve lacks: when demand
    suffices, a saturated link moves min(bw, sum of caps) even though some
    flows are capped — the capped flows' unused share is REDISTRIBUTED,
    not stranded. Demand abundance is forced (30 threads each, tpt >= 0.1,
    bw <= 2.0, so uncapped per-link demand >= 3 per stage > bw)."""
    F = data.draw(st.integers(2, 4))
    params = make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1],
                             cap=[2.0, 2.0], n_max=50)
    table = make_table(
        np.full((1, 3), data.draw(st.floats(0.1, 0.5)), np.float32),
        np.full((1, 3), data.draw(bw_st), np.float32), bin_seconds=1.0)
    caps = [data.draw(st.one_of(st.just(np.inf), st.floats(0.05, 1.5)))
            for _ in range(F)]
    obj = make_flow_objective(rate_cap=caps)
    threads = jnp.full((F, 3), 30.0)
    rates = np.asarray(_topology_substep_rates(
        params, single_link_graph(table), all_links_path(F, 1), threads,
        always_on(F), jnp.zeros(()), 2, obj))
    per_flow_cap = np.minimum(np.asarray(caps), 30.0 * 0.1)  # cap vs demand
    deliverable = min(float(np.asarray(table.bw).min()),
                      float(per_flow_cap.sum()))
    total = rates.sum(axis=1)  # (S, 3)
    np.testing.assert_allclose(total, deliverable, atol=1e-4, rtol=1e-4)
    # and caps are still individually honored
    assert (rates <= np.asarray(caps)[None, :, None] + 1e-5).all()


# ---------------------------------------------------------------------------
# Fleet scale-out: the sparse compact-active-set solve IS the dense solve
# ---------------------------------------------------------------------------

@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_sparse_fleet_interval_equals_dense(data):
    """For ANY fleet/schedule/objective draw and any static ``max_active``
    bound that honors the caller promise (>= the true concurrency), the
    compact gather->solve->scatter path returns the dense buffers and
    throughputs to float32 ulp noise (the order-preserving gather keeps
    the summand ORDER, but dropping a mid-fleet zero term shifts XLA's
    SIMD lane grouping — 1e-5 is thousands of ulps of margin), and the
    ungathered flows stay EXACTLY untouched."""
    params, table, flows, threads = data.draw(fleet_world())
    F = threads.shape[0]
    obj = data.draw(st.one_of(st.none(), objectives_for(F)))
    t0 = data.draw(st.floats(0.0, 2.0))
    buffers = jnp.asarray(
        [[data.draw(st.floats(0.0, 0.4)) for _ in range(2)]
         for _ in range(F)], jnp.float32)
    want_b, want_t = fleet_interval(params, buffers, threads, t0,
                                    flows=flows, table=table,
                                    substeps=SUBSTEPS, objectives=obj)
    # max_active = F is the honest bound for these draws (every window may
    # intersect the interval); padding the fleet makes it a REAL bound
    pad = data.draw(st.integers(1, 3))
    flows_p = make_flow_schedule(
        list(np.asarray(flows.t_start)) + [np.inf] * pad,
        list(np.asarray(flows.t_end)) + [np.inf] * pad)
    threads_p = jnp.concatenate([threads, jnp.ones((pad, 3))])
    buffers_p = jnp.concatenate([buffers, jnp.zeros((pad, 2))])
    from repro.core.fleet import pad_flow_objectives
    obj_p = pad_flow_objectives(obj, F + pad)
    got_b, got_t = fleet_interval(params, buffers_p, threads_p, t0,
                                  flows=flows_p, table=table,
                                  substeps=SUBSTEPS, objectives=obj_p,
                                  max_active=F)
    np.testing.assert_allclose(np.asarray(got_b[:F]), np.asarray(want_b),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_t[:F]), np.asarray(want_t),
                               atol=1e-5)
    # the padded flows moved exactly nothing
    assert np.asarray(got_b[F:]).max(initial=0.0) == 0.0
    assert np.asarray(got_t[F:]).max(initial=0.0) == 0.0


@given(data=st.data())
@settings(max_examples=100, deadline=None)
def test_sparse_topology_interval_matches_dense(data):
    """Topology twin: on a random 2-link graph with per-flow routes, the
    sparse path (compact gather + sorted water-fill) matches the dense
    solve at 1e-5 — ulp-level gather-lane reassociation when no finite
    caps exist (the water-fill is an exact no-op on both paths), plus the
    sorted fill reaching the F-round spill loop's fixed point in closed
    form when caps redistribute."""
    params, table, flows, threads = data.draw(fleet_world())
    F = threads.shape[0]
    E = 2
    graph = make_link_graph(
        jnp.stack([table.tpt, table.tpt * 0.8]),
        jnp.stack([table.bw, table.bw * 1.2]),
        bin_seconds=table.bin_seconds)
    onpath = jnp.asarray(
        [[data.draw(st.sampled_from([0.0, 1.0])) for _ in range(E)]
         for _ in range(F)], jnp.float32)
    paths = make_path_spec(onpath)
    capped = data.draw(st.booleans())
    obj = data.draw(objectives_for(F)) if capped else None
    t0 = data.draw(st.floats(0.0, 2.0))
    buffers = jnp.zeros((F, 2), jnp.float32)
    want_b, want_t = topology_interval(params, buffers, threads, t0,
                                       graph=graph, paths=paths,
                                       flows=flows, substeps=SUBSTEPS,
                                       objectives=obj)
    from repro.core.fleet import pad_flow_schedule, pad_flow_objectives
    from repro.core.topology import pad_path_spec
    flows_p = pad_flow_schedule(flows, F + 2)
    got_b, got_t = topology_interval(
        params, jnp.concatenate([buffers, jnp.zeros((2, 2))]),
        jnp.concatenate([threads, jnp.ones((2, 3))]), t0, graph=graph,
        paths=pad_path_spec(paths, F + 2), flows=flows_p,
        substeps=SUBSTEPS, objectives=pad_flow_objectives(obj, F + 2),
        max_active=F)
    np.testing.assert_allclose(np.asarray(got_b[:F]), np.asarray(want_b),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_t[:F]), np.asarray(want_t),
                               atol=1e-5)
    assert np.asarray(got_t[F:]).max(initial=0.0) == 0.0


@given(data=st.data())
@settings(max_examples=150, deadline=None)
def test_sorted_water_fill_matches_round_loop(data):
    """The O(A log A) sort-based water-fill reaches the same fixed point
    as the F-round spill loop for any draw: bitwise when no finite caps
    exist (both are exact no-ops), 1e-5 otherwise (same limit, different
    partial-sum order — the loop converges geometrically, the sort solves
    the breakpoint equation in closed form)."""
    params, table, flows, threads = data.draw(fleet_world())
    F = threads.shape[0]
    obj = data.draw(st.one_of(st.none(), objectives_for(F)))
    graph = single_link_graph(table)
    paths = all_links_path(F, 1)
    t0 = jnp.asarray(data.draw(st.floats(0.0, 2.0)), jnp.float32)
    loop = np.asarray(_topology_substep_rates(
        params, graph, paths, threads, flows, t0, SUBSTEPS, obj,
        water_fill="rounds"))
    srt = np.asarray(_topology_substep_rates(
        params, graph, paths, threads, flows, t0, SUBSTEPS, obj,
        water_fill="sorted"))
    has_finite_cap = obj is not None and bool(
        np.isfinite(np.asarray(obj.rate_cap)).any())
    if not has_finite_cap:
        assert np.array_equal(loop, srt)
    else:
        np.testing.assert_allclose(srt, loop, atol=1e-5)


@given(data=st.data())
@settings(max_examples=100, deadline=None)
def test_all_inactive_substeps_move_zero_bytes_every_path(data):
    """An interval no flow's window intersects moves EXACTLY zero bytes on
    every solve path — dense, sparse (whose gather comes back empty),
    and the fused kernel — for any draw, objectives included. This pins
    the trailing activity guard: without it the floor/share math can
    assign epsilon rates to inactive flows."""
    params, table, _, threads = data.draw(fleet_world())
    F = threads.shape[0]
    obj = data.draw(st.one_of(st.none(), objectives_for(F)))
    # every window strictly after the simulated interval [0, duration)
    flows = make_flow_schedule([float(params.duration) + 1.0] * F,
                               [np.inf] * F)
    # float32 levels without subnormals: XLA flushes those to zero in
    # arithmetic (CPU and TPU alike), so the dense path's "+ 0.0 moved"
    # would zero a level that the sparse path's pass-through keeps
    buffers = jnp.asarray(
        [[data.draw(st.floats(0.0, float(np.float32(0.4)), width=32,
                              allow_subnormal=False))
          for _ in range(2)] for _ in range(F)], jnp.float32)
    for kw in ({}, {"max_active": max(F - 1, 1)}, {"backend": "pallas"},
               {"backend": "pallas", "max_active": max(F - 1, 1)}):
        if kw.get("max_active", F) >= F:
            kw = {k: v for k, v in kw.items() if k != "max_active"}
        bufs, tps = fleet_interval(params, buffers, threads, 0.0,
                                   flows=flows, table=table,
                                   substeps=SUBSTEPS, objectives=obj, **kw)
        assert np.asarray(tps).max(initial=0.0) == 0.0, kw
        assert np.array_equal(np.asarray(bufs), np.asarray(buffers)), kw


# ---------------------------------------------------------------------------
# F=1 fleet == single-flow env, bit-for-bit, across randomized params
# ---------------------------------------------------------------------------

@given(tpt=st.tuples(*[rate_st] * 3), bw=st.tuples(*[bw_st] * 3),
       cap=st.floats(0.5, 3.0), seed=st.integers(0, 2 ** 16),
       action=st.tuples(*[st.floats(1.0, 40.0)] * 3))
@settings(max_examples=200, deadline=None)
def test_f1_fleet_step_equals_env_step_randomized(tpt, bw, cap, seed,
                                                  action):
    """The PR 4 pin, universally quantified: for ANY static parameters,
    reset key, and action, the F=1 fleet path reproduces the single-flow
    env bit-for-bit (share = n/n = 1.0 exactly)."""
    params = make_env_params(tpt=list(tpt), bw=list(bw), cap=[cap, cap],
                             n_max=50)
    key = jax.random.PRNGKey(seed)
    st_env = env_reset(params, key)
    st_fleet = fleet_reset(params, key, 1)
    a = jnp.asarray(action, jnp.float32)
    st_env2, obs, r = env_step(params, st_env, a)
    st_fleet2, fobs, fr = fleet_step(params, st_fleet, a[None])
    assert np.array_equal(np.asarray(st_env2.buffers),
                          np.asarray(st_fleet2.buffers[0]))
    assert np.array_equal(np.asarray(st_env2.throughputs),
                          np.asarray(st_fleet2.throughputs[0]))
    assert np.array_equal(np.asarray(obs), np.asarray(fobs[0]))
    assert float(r) == float(fr)


# ---------------------------------------------------------------------------
# Jain's index stays in (0, 1]
# ---------------------------------------------------------------------------

@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_jain_index_in_unit_interval(data):
    """For any goodput vector, activity mask, and priority weights, the
    (weighted) Jain index is finite and lives in (0, 1] — empty and
    all-zero fleets score exactly 1.0."""
    n = data.draw(st.integers(1, 6))
    x = jnp.asarray(data.draw(
        st.lists(st.floats(0.0, 5.0), min_size=n, max_size=n)), jnp.float32)
    active = data.draw(st.one_of(st.none(), st.lists(
        st.sampled_from([0.0, 1.0]), min_size=n, max_size=n)))
    weights = data.draw(st.one_of(st.none(), st.lists(
        st.sampled_from([1.0, 2.0, 4.0]), min_size=n, max_size=n)))
    j = float(jain_index(
        x, None if active is None else jnp.asarray(active, jnp.float32),
        None if weights is None else jnp.asarray(weights, jnp.float32)))
    assert np.isfinite(j)
    assert 0.0 < j <= 1.0 + 1e-6, j
    if float(jnp.asarray(x).sum()) == 0.0:
        assert j == 1.0


# ---------------------------------------------------------------------------
# Permutation equivariance of the shared policy
# ---------------------------------------------------------------------------

_POLICY = None


def _policy():
    global _POLICY
    if _POLICY is None:
        _POLICY = nets.policy_init(jax.random.PRNGKey(7),
                                   obs_dim=FLEET_OBS.dim)
    return _POLICY


@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_fleet_is_permutation_equivariant(data):
    """Relabeling the flows relabels the outputs and changes nothing else:
    observation rows, next-state rows, and the shared policy's action rows
    permute with the fleet; the shared reward is invariant. (Float sums
    reassociate under permutation, hence tolerance instead of atol=0.)"""
    F = 3
    params, table, flows, threads = data.draw(fleet_world(n_flows=F))
    perm = data.draw(st.permutations(list(range(F))))
    perm = np.asarray(perm)
    buffers = jnp.asarray(
        [[data.draw(st.floats(0.0, 0.4)) for _ in range(2)]
         for _ in range(F)], jnp.float32)
    tps0 = jnp.asarray(
        [[data.draw(st.floats(0.0, 1.0)) for _ in range(3)]
         for _ in range(F)], jnp.float32)
    state = FleetState(buffers=buffers, threads=threads, throughputs=tps0,
                       t=jnp.asarray(0.0, jnp.float32),
                       prev_throughputs=tps0,
                       delivered=jnp.zeros((F,), jnp.float32))
    state_p = FleetState(buffers=buffers[perm], threads=threads[perm],
                         throughputs=tps0[perm], t=state.t,
                         prev_throughputs=tps0[perm],
                         delivered=state.delivered[perm])
    flows_p = make_flow_schedule(np.asarray(flows.t_start)[perm],
                                 np.asarray(flows.t_end)[perm])

    obs = np.asarray(fleet_observe(params, state, flows=flows, table=table,
                                   spec=FLEET_OBS))
    obs_p = np.asarray(fleet_observe(params, state_p, flows=flows_p,
                                     table=table, spec=FLEET_OBS))
    np.testing.assert_allclose(obs_p, obs[perm], atol=1e-5, rtol=1e-5)

    # the shared policy maps row f of the observation to row f of the
    # action — permuting its input permutes its output
    mean, _ = nets.policy_apply(_policy(), jnp.asarray(obs))
    mean_p, _ = nets.policy_apply(_policy(), jnp.asarray(obs[perm]))
    np.testing.assert_allclose(np.asarray(mean_p), np.asarray(mean)[perm],
                               atol=1e-4, rtol=1e-4)

    actions = jnp.clip(mean, 1.0, 50.0)
    s2, o2, r = fleet_step(params, state, actions, flows=flows, table=table,
                           substeps=SUBSTEPS, fairness_coef=0.5)
    s2p, o2p, rp = fleet_step(params, state_p, actions[perm], flows=flows_p,
                              table=table, substeps=SUBSTEPS,
                              fairness_coef=0.5)
    np.testing.assert_allclose(np.asarray(s2p.throughputs),
                               np.asarray(s2.throughputs)[perm],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s2p.delivered),
                               np.asarray(s2.delivered)[perm],
                               atol=1e-5, rtol=1e-5)
    assert float(rp) == pytest.approx(float(r), abs=1e-4)


@given(data=st.data())
@settings(max_examples=100, deadline=None)
def test_sparse_observe_and_reward_equal_dense(data):
    """PR 9 property: for ANY fleet/schedule/objective draw, the sparse
    full step (solve + observe + reward on the compact active set) matches
    the dense step — reward to 1e-5 (the Jain/deadline sums reassociate
    over A instead of F lanes), next state to 1e-6, observation rows of
    flows intersecting the forward observe window to 2e-6 with everything
    else EXACTLY zero (the spec'd sparse-observe semantics)."""
    from repro.core.simulator import OBJECTIVE_OBS
    params, table, flows, threads = data.draw(fleet_world())
    F = threads.shape[0]
    obj = data.draw(st.one_of(st.none(), objectives_for(F)))
    pad = data.draw(st.integers(1, 3))
    flows_p = make_flow_schedule(
        list(np.asarray(flows.t_start)) + [np.inf] * pad,
        list(np.asarray(flows.t_end)) + [np.inf] * pad)
    from repro.core.fleet import pad_flow_objectives
    obj_p = pad_flow_objectives(obj, F + pad)
    state = fleet_reset(params, jax.random.PRNGKey(data.draw(
        st.integers(0, 2 ** 16))), F + pad,
        t0=data.draw(st.floats(0.0, 1.5)), flows=flows_p, table=table,
        substeps=SUBSTEPS)
    acts = jnp.asarray(
        [[data.draw(st.floats(1.0, 30.0)) for _ in range(3)]
         for _ in range(F + pad)], jnp.float32)
    fair = data.draw(st.sampled_from([0.0, 0.3]))
    d_state, d_obs, d_rew = fleet_step(
        params, state, acts, flows=flows_p, table=table,
        substeps=SUBSTEPS, spec=OBJECTIVE_OBS, objectives=obj_p,
        fairness_coef=fair)
    s_state, s_obs, s_rew = fleet_step(
        params, state, acts, flows=flows_p, table=table,
        substeps=SUBSTEPS, spec=OBJECTIVE_OBS, objectives=obj_p,
        fairness_coef=fair, max_active=F)
    np.testing.assert_allclose(float(s_rew), float(d_rew), rtol=1e-5,
                               atol=1e-5)
    for a, b in zip(s_state, d_state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    t, d = float(d_state.t), float(params.duration)
    hit = ((np.asarray(flows_p.t_start) < t + d)
           & (np.asarray(flows_p.t_end) > t))
    s_obs, d_obs = np.asarray(s_obs), np.asarray(d_obs)
    np.testing.assert_allclose(s_obs[hit], d_obs[hit], atol=2e-6)
    assert np.abs(s_obs[~hit]).max(initial=0.0) == 0.0
