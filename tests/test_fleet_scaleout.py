"""Fleet scale-out: the sparse compact-active-set solve, the fused Pallas
contention kernel, power-of-two flow padding, and sharded fleets.

The dense solve is the reference; everything here pins the fast paths
against it — bitwise where the summation order provably survives (the
order-preserving gather), at justified tolerance where it genuinely
changes (the kernel's fused arithmetic, the sorted water-fill's closed
form). These are the deterministic (seeded-loop) twins of the hypothesis
properties in tests/test_fleet_properties.py, so the invariants are
exercised even on images without hypothesis."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.fleet import (FlowSchedule, FleetState, make_flow_schedule,
                              make_flow_objective, always_on, fleet_interval,
                              fleet_reset, fleet_step, flow_bucket,
                              max_concurrent_flows, pad_flow_schedule,
                              pad_flow_objectives, default_objectives,
                              _fleet_substep_rates, _window_flow_ids)
from repro.core.schedule import make_table
from repro.core.simulator import make_env_params
from repro.core.topology import (single_link_graph, all_links_path,
                                 make_link_graph, make_path_spec,
                                 pad_path_spec, topology_interval,
                                 _topology_substep_rates)
from repro.kernels.contention.ops import contention_rates
from repro.kernels.contention.ref import contention_rates_reference

SUBSTEPS = 6


def _params():
    return make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def _world(seed, F=6):
    """Seeded random fleet world: 2-bin schedule, activity windows around
    the simulated interval, mixed finite/inf caps."""
    rng = np.random.default_rng(seed)
    params = _params()
    table = make_table(rng.uniform(0.02, 0.5, (2, 3)).astype(np.float32),
                       rng.uniform(0.1, 2.0, (2, 3)).astype(np.float32),
                       bin_seconds=0.5)
    t_start = rng.uniform(0.0, 1.5, F)
    flows = make_flow_schedule(t_start, t_start + rng.uniform(0.1, 2.0, F))
    threads = jnp.asarray(rng.integers(1, 30, (F, 3)), jnp.float32)
    caps = np.where(rng.random(F) < 0.5, np.inf,
                    rng.uniform(0.05, 1.5, F))
    obj = make_flow_objective(weight=rng.choice([1.0, 2.0, 4.0], F),
                              rate_floor=rng.uniform(0.0, 1.5, F),
                              rate_cap=caps)
    return params, table, flows, threads, obj


# ---------------------------------------------------------------------------
# Bucketing / concurrency sizing units
# ---------------------------------------------------------------------------

def test_flow_bucket_grid():
    assert [flow_bucket(n) for n in (0, 1, 2, 3, 4, 5, 8, 9, 4096, 4097)] \
        == [1, 1, 2, 4, 4, 8, 8, 16, 4096, 8192]


def test_max_concurrent_flows_event_sweep():
    # windows: [0,2) [1,3) [5,6) -> instantaneous peak 2; only an interval
    # longer than 3s (e.g. [1.9, 5.4)) can intersect all three at once
    flows = make_flow_schedule([0.0, 1.0, 5.0], [2.0, 3.0, 6.0])
    assert max_concurrent_flows(flows) == 2
    assert max_concurrent_flows(flows, window=3.0) == 2
    assert max_concurrent_flows(flows, window=3.5) == 3
    # batched schedules: the max over the batch
    b = FlowSchedule(t_start=jnp.zeros((2, 4)), t_end=jnp.full((2, 4), 1.0))
    assert max_concurrent_flows(b) == 4
    # never-active padding does not count
    assert max_concurrent_flows(pad_flow_schedule(flows, 8)) == 2


def test_window_flow_ids_empty_set():
    """The compact gather of an interval nobody intersects is all fill
    (== F), which the scatter drops — the empty-active-set guard."""
    flows = make_flow_schedule([5.0, 6.0], [7.0, 8.0])
    idx = np.asarray(_window_flow_ids(flows, jnp.float32(0.0), 1.0, 2))
    assert (idx == 2).all()


# ---------------------------------------------------------------------------
# Sparse == dense (the deterministic twin of the hypothesis property)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("with_obj", [False, True])
def test_sparse_fleet_interval_matches_dense(seed, with_obj):
    """Tolerance justification: the gather is order-preserving, but when a
    mid-fleet flow's window misses the interval its ZERO term vanishes
    from the cross-flow reductions, shifting XLA's SIMD lane grouping —
    partial sums reassociate by a few float32 ulps (~6e-8 observed).
    1e-6 is ~10x that; the ungathered flows stay EXACTLY untouched."""
    params, table, flows, threads, obj = _world(seed)
    obj = obj if with_obj else None
    F = flows.n_flows
    rng = np.random.default_rng(seed + 100)
    buffers = jnp.asarray(rng.uniform(0.0, 0.4, (F, 2)), jnp.float32)
    t0 = float(rng.uniform(0.0, 2.0))
    want_b, want_t = fleet_interval(params, buffers, threads, t0,
                                    flows=flows, table=table,
                                    substeps=SUBSTEPS, objectives=obj)
    # pad so max_active=F is a REAL bound (< padded fleet size)
    flows_p = pad_flow_schedule(flows, F + 2)
    got_b, got_t = fleet_interval(
        params, jnp.concatenate([buffers, jnp.zeros((2, 2))]),
        jnp.concatenate([threads, jnp.ones((2, 3))]), t0, flows=flows_p,
        table=table, substeps=SUBSTEPS,
        objectives=pad_flow_objectives(obj, F + 2), max_active=F)
    np.testing.assert_allclose(np.asarray(got_b[:F]), np.asarray(want_b),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_t[:F]), np.asarray(want_t),
                               atol=1e-6)
    assert np.asarray(got_t[F:]).max() == 0.0
    assert np.asarray(got_b[F:]).max() == 0.0


@pytest.mark.parametrize("seed", range(6))
def test_sparse_topology_interval_matches_dense(seed):
    """2-link graph with random routes: 1e-6 when no finite caps (both
    water-fills are exact no-ops; the ulp noise is the same gather-lane
    reassociation as the fleet test), 1e-5 when caps redistribute (the
    sorted fill reaches the F-round loop's fixed point in closed form)."""
    params, table, flows, threads, obj = _world(seed)
    F = flows.n_flows
    graph = make_link_graph(jnp.stack([table.tpt, table.tpt * 0.8]),
                            jnp.stack([table.bw, table.bw * 1.2]),
                            bin_seconds=0.5)
    rng = np.random.default_rng(seed + 200)
    onpath = rng.integers(0, 2, (F, 2)).astype(np.float32)
    paths = make_path_spec(onpath)
    use_caps = seed % 2 == 0
    o = obj if use_caps else None
    want_b, want_t = topology_interval(params, jnp.zeros((F, 2)), threads,
                                       0.3, graph=graph, paths=paths,
                                       flows=flows, substeps=SUBSTEPS,
                                       objectives=o)
    got_b, got_t = topology_interval(
        params, jnp.zeros((F + 2, 2)),
        jnp.concatenate([threads, jnp.ones((2, 3))]), 0.3, graph=graph,
        paths=pad_path_spec(paths, F + 2),
        flows=pad_flow_schedule(flows, F + 2), substeps=SUBSTEPS,
        objectives=pad_flow_objectives(o, F + 2), max_active=F)
    if o is None or not np.isfinite(np.asarray(o.rate_cap)).any():
        np.testing.assert_allclose(np.asarray(got_t[:F]),
                                   np.asarray(want_t), atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_b[:F]),
                                   np.asarray(want_b), atol=1e-6)
    else:
        np.testing.assert_allclose(np.asarray(got_t[:F]),
                                   np.asarray(want_t), atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_b[:F]),
                                   np.asarray(want_b), atol=1e-5)
    assert np.asarray(got_t[F:]).max() == 0.0


@pytest.mark.parametrize("seed", range(6))
def test_sorted_water_fill_matches_round_loop(seed):
    params, table, flows, threads, obj = _world(seed)
    F = flows.n_flows
    graph, paths = single_link_graph(table), all_links_path(F, 1)
    loop = np.asarray(_topology_substep_rates(
        params, graph, paths, threads, flows, jnp.float32(0.2), SUBSTEPS,
        obj, water_fill="rounds"))
    srt = np.asarray(_topology_substep_rates(
        params, graph, paths, threads, flows, jnp.float32(0.2), SUBSTEPS,
        obj, water_fill="sorted"))
    np.testing.assert_allclose(srt, loop, atol=1e-5)
    # no finite caps: both fills are exact no-ops -> bitwise
    nc = make_flow_objective(rate_floor=np.asarray(obj.rate_floor))
    loop_nc = np.asarray(_topology_substep_rates(
        params, graph, paths, threads, flows, jnp.float32(0.2), SUBSTEPS,
        nc, water_fill="rounds"))
    srt_nc = np.asarray(_topology_substep_rates(
        params, graph, paths, threads, flows, jnp.float32(0.2), SUBSTEPS,
        nc, water_fill="sorted"))
    assert np.array_equal(loop_nc, srt_nc)


@pytest.mark.parametrize("seed", range(4))
def test_all_inactive_interval_moves_zero_bytes_every_path(seed):
    """The epsilon-guard small fix, pinned: an interval no flow's window
    intersects moves EXACTLY zero bytes on the dense, sparse (empty
    gather), and pallas paths alike — objectives included."""
    params, table, _, threads, obj = _world(seed)
    F = threads.shape[0]
    flows = make_flow_schedule([float(params.duration) + 1.0] * F,
                               [np.inf] * F)
    rng = np.random.default_rng(seed)
    buffers = jnp.asarray(rng.uniform(0.0, 0.4, (F, 2)), jnp.float32)
    for kw in ({}, {"max_active": F - 1}, {"backend": "pallas"},
               {"backend": "pallas", "max_active": F - 1}):
        for o in (None, obj):
            bufs, tps = fleet_interval(params, buffers, threads, 0.0,
                                       flows=flows, table=table,
                                       substeps=SUBSTEPS, objectives=o,
                                       **kw)
            assert np.asarray(tps).max() == 0.0, (kw, o is None)
            assert np.array_equal(np.asarray(bufs), np.asarray(buffers)), kw


# ---------------------------------------------------------------------------
# Fused contention kernel: pallas (interpret on CPU) vs reference vs core
# ---------------------------------------------------------------------------

def _kernel_operands(seed, F=5, E=2, S=4):
    rng = np.random.default_rng(seed)
    threads = jnp.asarray(rng.integers(1, 30, (F, 3)), jnp.float32)
    act = jnp.asarray(rng.integers(0, 2, (S, F)), jnp.float32)
    onpath = jnp.asarray(rng.integers(0, 2, (S, F, E)), jnp.float32)
    tpt = jnp.asarray(rng.uniform(0.02, 0.5, (S, E, 3)), jnp.float32)
    bw = jnp.asarray(rng.uniform(0.1, 2.0, (S, E, 3)), jnp.float32)
    floor = jnp.asarray(rng.uniform(0.0, 1.5, F), jnp.float32)
    cap = jnp.asarray(np.where(rng.random(F) < 0.5, np.inf,
                               rng.uniform(0.05, 1.5, F)), jnp.float32)
    return threads, act, onpath, tpt, bw, floor, cap


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("rounds", [0, 5])
def test_kernel_matches_reference(seed, rounds):
    threads, act, onpath, tpt, bw, floor, cap = _kernel_operands(seed)
    for fl, cp in ((None, None), (floor, cap)):
        want = np.asarray(contention_rates_reference(
            threads, act, onpath, tpt, bw, fl, cp, rounds=rounds))
        got = np.asarray(contention_rates(
            threads, act, onpath, tpt, bw, fl, cp, rounds=rounds))
        assert want.shape == got.shape == (4, 5, 3)
        # interpret-mode pallas reassociates the reductions -> float32 ulp
        # noise around ~1.0-scale rates; 2e-5 is ~tens of ulps
        np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("with_obj", [False, True])
def test_fleet_pallas_backend_matches_dense(with_obj):
    params, table, flows, threads, obj = _world(3)
    o = obj if with_obj else None
    want = np.asarray(_fleet_substep_rates(params, table, threads, flows,
                                           jnp.float32(0.4), SUBSTEPS, o))
    F = flows.n_flows
    got_b, got_t = fleet_interval(params, jnp.zeros((F, 2)), threads, 0.4,
                                  flows=flows, table=table,
                                  substeps=SUBSTEPS, objectives=o,
                                  backend="pallas")
    ref_b, ref_t = fleet_interval(params, jnp.zeros((F, 2)), threads, 0.4,
                                  flows=flows, table=table,
                                  substeps=SUBSTEPS, objectives=o)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(ref_t),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_b), np.asarray(ref_b),
                               atol=2e-5)
    assert want.shape == (SUBSTEPS, F, 3)


def test_topology_pallas_backend_matches_dense():
    params, table, flows, threads, obj = _world(4)
    F = flows.n_flows
    graph = make_link_graph(jnp.stack([table.tpt, table.tpt * 0.8]),
                            jnp.stack([table.bw, table.bw * 1.2]),
                            bin_seconds=0.5)
    paths = all_links_path(F, 2)
    for o in (None, obj):
        ref_b, ref_t = topology_interval(params, jnp.zeros((F, 2)), threads,
                                         0.4, graph=graph, paths=paths,
                                         flows=flows, substeps=SUBSTEPS,
                                         objectives=o)
        got_b, got_t = topology_interval(params, jnp.zeros((F, 2)), threads,
                                         0.4, graph=graph, paths=paths,
                                         flows=flows, substeps=SUBSTEPS,
                                         objectives=o, backend="pallas")
        np.testing.assert_allclose(np.asarray(got_t), np.asarray(ref_t),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_b), np.asarray(ref_b),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# Power-of-two padding: reward-exact, and compile count stays flat
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
def test_padding_is_reward_exact(seed):
    """fleet_step on a fleet padded to the next bucket returns the SAME
    reward and the same per-flow state rows for the real flows — the
    padded rows never activate, move nothing, and score zero utility."""
    params, table, flows, threads, obj = _world(seed, F=6)
    F = flows.n_flows
    key = jax.random.PRNGKey(seed)
    state = fleet_reset(params, key, F, flows=flows, table=table,
                        substeps=SUBSTEPS)
    acts = jnp.asarray(
        np.random.default_rng(seed).uniform(1, 40, (F, 3)), jnp.float32)
    s2, _, r = fleet_step(params, state, acts, flows=flows, table=table,
                          substeps=SUBSTEPS, objectives=obj,
                          fairness_coef=0.5)
    P = flow_bucket(F + 1)  # 8
    state_p = FleetState(
        buffers=jnp.concatenate([state.buffers, jnp.zeros((P - F, 2))]),
        threads=jnp.concatenate([state.threads, jnp.ones((P - F, 3))]),
        throughputs=jnp.concatenate([state.throughputs,
                                     jnp.zeros((P - F, 3))]),
        t=state.t,
        prev_throughputs=jnp.concatenate([state.prev_throughputs,
                                          jnp.zeros((P - F, 3))]),
        delivered=jnp.concatenate([state.delivered, jnp.zeros((P - F,))]))
    acts_p = jnp.concatenate([acts, jnp.ones((P - F, 3))])
    s2p, _, rp = fleet_step(params, state_p, acts_p,
                            flows=pad_flow_schedule(flows, P), table=table,
                            substeps=SUBSTEPS,
                            objectives=pad_flow_objectives(obj, P),
                            fairness_coef=0.5)
    assert float(r) == float(rp)
    assert np.array_equal(np.asarray(s2.throughputs),
                          np.asarray(s2p.throughputs[:F]))
    assert np.asarray(s2p.throughputs[F:]).max() == 0.0


def test_compile_count_flat_across_padded_resamples():
    """The regression the padding exists to prevent: resampling fleets of
    VARYING n_flows inside one bucket hits a single fleet_step compile
    once batches are padded (one XLA shape for the whole bucket)."""
    from repro.scenarios import sample_fleet_batch
    params = _params()
    base = fleet_step._cache_size()
    compiles = []
    for rnd, n in enumerate([5, 6, 8, 7]):  # all bucket to 8
        _, tables, flows, objs = sample_fleet_batch(
            2, n, seed=rnd, objective_mix=True, pad_flows=True)
        assert flows.n_flows == 8 and objs.n_flows == 8
        F = flows.n_flows
        key = jax.random.PRNGKey(rnd)
        step = jax.vmap(lambda tab, fl, ob: fleet_step(
            params,
            FleetState(buffers=jnp.zeros((F, 2)),
                       threads=jnp.full((F, 3), 8.0),
                       throughputs=jnp.zeros((F, 3)),
                       t=jnp.float32(0.0),
                       prev_throughputs=jnp.zeros((F, 3)),
                       delivered=jnp.zeros((F,))),
            jnp.full((F, 3), 8.0), flows=fl, table=tab, substeps=SUBSTEPS,
            objectives=ob)[2])
        r = step(tables, flows, objs)
        jax.block_until_ready(r)
        compiles.append(fleet_step._cache_size() - base)
    # one trace for the whole bucket: round 1 compiled it, rounds 2-4 hit
    assert compiles == [compiles[0]] * 4, compiles


# ---------------------------------------------------------------------------
# Sharded fleets
# ---------------------------------------------------------------------------

def test_fleet_mesh_single_device_is_bitwise_noop():
    """On one device every flow_sharding spec degenerates to replication:
    the sharded step returns the unsharded result bitwise (the same code
    path multi-device runs distributed)."""
    from repro.launch.mesh import make_fleet_mesh
    from repro.sharding.fleet import (flow_sharding, shard_flow_schedule,
                                      shard_flow_objectives,
                                      shard_fleet_state)
    params, table, flows, threads, obj = _world(5)
    F = flows.n_flows
    mesh = make_fleet_mesh(1)
    assert flow_sharding(mesh, 2, -1, F).is_fully_replicated
    key = jax.random.PRNGKey(0)
    state = fleet_reset(params, key, F, flows=flows, table=table,
                        substeps=SUBSTEPS)
    acts = jnp.full((F, 3), 8.0)
    s2, obs, r = fleet_step(params, state, acts, flows=flows, table=table,
                            substeps=SUBSTEPS, objectives=obj)
    s2s, obss, rs = fleet_step(params, shard_fleet_state(state, mesh), acts,
                               flows=shard_flow_schedule(flows, mesh),
                               table=table, substeps=SUBSTEPS,
                               objectives=shard_flow_objectives(obj, mesh))
    assert float(r) == float(rs)
    assert np.array_equal(np.asarray(obs), np.asarray(obss))
    assert np.array_equal(np.asarray(s2.buffers), np.asarray(s2s.buffers))
    assert shard_flow_objectives(None, mesh) is None


def test_fleet_mesh_indivisible_falls_back_to_replication():
    from repro.launch.mesh import make_fleet_mesh
    from repro.sharding.fleet import flow_sharding
    mesh = make_fleet_mesh(1)
    # 1 device divides anything; fake the check with a flow count of 0
    s = flow_sharding(mesh, 2, -1, 7)
    assert s.mesh.axis_names == ("flows",)


def test_sharded_fleet_step_multi_device_subprocess():
    """The real thing: 4 host-platform devices (XLA_FLAGS), the F axis
    sharded 4 ways, fleet_step under GSPMD == the unsharded result to
    float32 ulp noise (cross-shard reductions lower to a psum tree whose
    association differs from the single-device sequential sum — 1 ulp
    observed, 1e-6 pinned). A subprocess because the device count is
    fixed at jax import."""
    src = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.fleet import (make_flow_schedule, fleet_reset,
                                      fleet_step, make_flow_objective)
        from repro.core.schedule import make_table
        from repro.core.simulator import make_env_params
        from repro.launch.mesh import make_fleet_mesh
        from repro.sharding.fleet import (shard_flow_schedule,
                                          shard_flow_objectives,
                                          shard_fleet_state, flow_sharding)
        assert jax.device_count() == 4, jax.devices()
        F = 8
        params = make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1],
                                 cap=[2, 2], n_max=50)
        rng = np.random.default_rng(0)
        table = make_table(rng.uniform(0.05, 0.5, (2, 3)).astype('f'),
                           rng.uniform(0.5, 2.0, (2, 3)).astype('f'),
                           bin_seconds=0.5)
        ts = rng.uniform(0.0, 1.0, F)
        flows = make_flow_schedule(ts, ts + rng.uniform(0.5, 2.0, F))
        obj = make_flow_objective(rate_floor=rng.uniform(0, 1, F),
                                  rate_cap=np.where(rng.random(F) < 0.5,
                                                    np.inf, 0.8))
        state = fleet_reset(params, jax.random.PRNGKey(0), F, flows=flows,
                            table=table, substeps=6)
        acts = jnp.full((F, 3), 8.0)
        s2, obs, r = fleet_step(params, state, acts, flows=flows,
                                table=table, substeps=6, objectives=obj)
        mesh = make_fleet_mesh()
        sh = flow_sharding(mesh, 2, -2, F)
        assert not sh.is_fully_replicated  # really 4-way on the F axis
        s2s, obss, rs = fleet_step(
            params, shard_fleet_state(state, mesh), acts,
            flows=shard_flow_schedule(flows, mesh), table=table,
            substeps=6, objectives=shard_flow_objectives(obj, mesh))
        np.testing.assert_allclose(np.asarray(obss), np.asarray(obs),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(s2s.buffers),
                                   np.asarray(s2.buffers), atol=1e-6)
        np.testing.assert_allclose(np.asarray(s2s.throughputs),
                                   np.asarray(s2.throughputs), atol=1e-6)
        assert abs(float(r) - float(rs)) < 1e-5
        print("SHARDED-OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"),
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", src], env=env, cwd=None,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "SHARDED-OK" in out.stdout


# ---------------------------------------------------------------------------
# train_ppo integration: max_active + pad_flows + mesh
# ---------------------------------------------------------------------------

def test_train_ppo_scaleout_knobs_smoke():
    from repro.core.ppo import PPOConfig, train_ppo
    from repro.launch.mesh import make_fleet_mesh
    from repro.scenarios import sample_fleet_batch
    params = _params()
    _, tables, flows, objs = sample_fleet_batch(2, 6, seed=3,
                                                objective_mix=True,
                                                pad_flows=True)
    cfg = PPOConfig(n_flows=6, n_envs=2, max_episodes=2, max_steps=3,
                    pad_flows=True, max_active=4, log_every=0)
    res = train_ppo(params, cfg, tables=tables, flows=flows,
                    objectives=objs, mesh=make_fleet_mesh(1))
    assert res.episodes == 2
    assert np.isfinite(res.best_reward)


# ---------------------------------------------------------------------------
# Sparse observe + reward == dense (PR 9: the full per-step cost is O(A*E))
# ---------------------------------------------------------------------------

def _obs_world(seed, F=24, A=16):
    """A wider seeded world where the active-set bound genuinely bites
    (A < F): Poisson-ish staggered windows, mixed tiers/deadlines/demands
    so every reward term is exercised."""
    rng = np.random.default_rng(seed)
    params = _params()
    table = make_table(rng.uniform(0.05, 0.4, (2, 3)).astype(np.float32),
                       rng.uniform(0.3, 1.5, (2, 3)).astype(np.float32),
                       bin_seconds=0.5)
    t_start = rng.uniform(0.0, 6.0, F)
    flows = make_flow_schedule(t_start, t_start + rng.uniform(0.2, 1.5, F))
    obj = make_flow_objective(
        F, tiers=rng.choice(["gold", "silver", "bronze"], F),
        deadline=np.where(rng.random(F) < 0.5,
                          rng.uniform(1.0, 8.0, F), np.inf),
        demand=np.where(rng.random(F) < 0.5,
                        rng.uniform(0.5, 4.0, F), np.inf))
    assert flow_bucket(max_concurrent_flows(
        flows, window=float(params.duration))) <= A < F
    return params, table, flows, obj


def _row_parity(sparse_obs, dense_obs, hit, atol=2e-6):
    """Gathered rows match dense; ungathered rows are EXACTLY zero (the
    spec'd sparse-observe semantics: a flow outside the observe window is
    all-zeros, not the dense path's resting-state row)."""
    np.testing.assert_allclose(sparse_obs[hit], dense_obs[hit], atol=atol)
    assert np.abs(sparse_obs[~hit]).max(initial=0.0) == 0.0


@pytest.mark.parametrize("seed", range(4))
def test_sparse_fleet_observe_matches_dense(seed):
    """fleet_observe(max_active=A): rows of flows whose window intersects
    the forward observe window [t, t+duration) equal the dense observation
    to gather-lane ulp noise; everything else is exactly zero."""
    from repro.core.fleet import fleet_observe
    from repro.core.simulator import ObservationSpec
    params, table, flows, obj = _obs_world(seed)
    spec = ObservationSpec(context=True, fleet=True, objectives=True)
    state = fleet_reset(params, jax.random.PRNGKey(seed), flows.n_flows,
                        t0=1.0, flows=flows, table=table,
                        substeps=SUBSTEPS)
    dense = np.asarray(fleet_observe(params, state, flows=flows,
                                     table=table, spec=spec,
                                     objectives=obj))
    sparse = np.asarray(fleet_observe(params, state, flows=flows,
                                      table=table, spec=spec,
                                      objectives=obj, max_active=16))
    t = float(state.t)
    d = float(params.duration)
    hit = (np.asarray(flows.t_start) < t + d) & (np.asarray(flows.t_end) > t)
    assert hit.any() and not hit.all()
    _row_parity(sparse, dense, hit)


@pytest.mark.parametrize("seed", range(4))
def test_sparse_fleet_step_obs_and_reward_match_dense(seed):
    """The full jitted step — solve + observe + reward — with
    ``max_active`` set: same next state (1e-6), same reward (1e-5: the
    Jain/deadline sums reassociate over A instead of F lanes), and
    row-parity on the observation."""
    from repro.core.simulator import ObservationSpec
    params, table, flows, obj = _obs_world(seed)
    spec = ObservationSpec(context=True, fleet=True, objectives=True)
    state = fleet_reset(params, jax.random.PRNGKey(seed), flows.n_flows,
                        t0=0.5, flows=flows, table=table,
                        substeps=SUBSTEPS)
    rng = np.random.default_rng(seed)
    for step in range(3):
        acts = jnp.asarray(rng.uniform(1.0, 30.0, (flows.n_flows, 3)),
                           jnp.float32)
        d_state, d_obs, d_rew = fleet_step(
            params, state, acts, flows=flows, table=table,
            substeps=SUBSTEPS, spec=spec, objectives=obj,
            fairness_coef=0.3)
        s_state, s_obs, s_rew = fleet_step(
            params, state, acts, flows=flows, table=table,
            substeps=SUBSTEPS, spec=spec, objectives=obj,
            fairness_coef=0.3, max_active=16)
        np.testing.assert_allclose(float(s_rew), float(d_rew), rtol=1e-5,
                                   atol=1e-5)
        for a, b in zip(s_state, d_state):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        t = float(d_state.t)
        d = float(params.duration)
        hit = ((np.asarray(flows.t_start) < t + d)
               & (np.asarray(flows.t_end) > t))
        _row_parity(np.asarray(s_obs), np.asarray(d_obs), hit)
        state = d_state


@pytest.mark.parametrize("seed", range(3))
def test_sparse_topology_step_obs_and_reward_match_dense(seed):
    """Topology twin: the sparse observe also rebuilds the TOPOLOGY block
    (bottleneck util / path length / my-share) from the compact set."""
    from repro.core.simulator import ObservationSpec
    from repro.core.topology import topology_reset, topology_step
    params, table, flows, obj = _obs_world(seed + 10)
    F = flows.n_flows
    spec = ObservationSpec(context=True, fleet=True, objectives=True,
                           topology=True)
    graph = make_link_graph(jnp.stack([table.tpt, table.tpt * 0.8]),
                            jnp.stack([table.bw, table.bw * 1.2]),
                            bin_seconds=0.5)
    rng = np.random.default_rng(seed + 10)
    onpath = np.maximum(rng.integers(0, 2, (F, 2)),
                        np.eye(2)[rng.integers(0, 2, F)]).astype(np.float32)
    paths = make_path_spec(jnp.asarray(onpath))
    state = topology_reset(params, jax.random.PRNGKey(seed), F, t0=0.5,
                           graph=graph, paths=paths, flows=flows,
                           substeps=SUBSTEPS)
    acts = jnp.asarray(rng.uniform(1.0, 30.0, (F, 3)), jnp.float32)
    d_state, d_obs, d_rew = topology_step(
        params, state, acts, graph=graph, paths=paths, flows=flows,
        substeps=SUBSTEPS, spec=spec, objectives=obj, fairness_coef=0.3)
    s_state, s_obs, s_rew = topology_step(
        params, state, acts, graph=graph, paths=paths, flows=flows,
        substeps=SUBSTEPS, spec=spec, objectives=obj, fairness_coef=0.3,
        max_active=16)
    np.testing.assert_allclose(float(s_rew), float(d_rew), rtol=1e-5,
                               atol=1e-5)
    for a, b in zip(s_state, d_state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    t = float(d_state.t)
    d = float(params.duration)
    hit = (np.asarray(flows.t_start) < t + d) & (np.asarray(flows.t_end) > t)
    _row_parity(np.asarray(s_obs), np.asarray(d_obs), hit)
