"""Bring-up smoke run of the AutoMDT main path on a TPU, in one process.

    python chip_smoke.py                # phases A-C on one chip
    python chip_smoke.py --four-chips   # sharded fleet training, 4 chips

Phase A trains the topology fleet (``Workload`` -> ``train_ppo``) at F=4096
flows over E=8 links with the sparse active-set solve. Phase B trains the
paper's single-flow agent on both substep backends, runs the Pallas
kernels compiled (their names must be in the compiled program) and checks
them against the jnp path on the chip. Phase C drives live
``TransferEngine``s on a ``SharedLink`` with phase A's policy through the
controller, then steps the policy for a 4096-flow observation.
``--four-chips`` runs only the flow-sharded fleet trainer and its one-chip
comparison.

The lines before the last are bring-up notes (shapes, compile seconds,
step wall times, peak device memory), not benchmark figures. The last line
is one JSON object naming the device. Any failed check exits non-zero
without it, and so does a host whose first JAX device is not a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.kernels import compiled_kernel_in  # noqa: E402

PLATFORM = "tpu"     # the only platform this script runs on
MB = 1 << 20
N_LINKS = 8
HORIZON = 60.0
HOLD_FRAC = 0.01     # each flow holds its path 1% of the horizon
ROUNDS = 3           # PPO rounds per training run


def note(msg):
    print(msg, flush=True)


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def env_params():
    from repro.core.simulator import make_env_params
    return make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1], cap=[2, 2],
                           n_max=50)


def topology_workload(n_envs, n_flows, seed=0):
    """``sample_topology_batch`` graphs and routes under Globus-style sparse
    instantaneous activity: Poisson arrivals whose flows each hold for
    ``HOLD_FRAC`` of the horizon (the sampler's own Poisson family keeps
    every flow active to the end, so all F would be concurrent)."""
    from repro.core.fleet import (flow_bucket, pad_flow_schedule,
                                  stack_flow_schedules)
    from repro.scenarios import sample_topology_batch
    from repro.scenarios.spec import arrival_schedule
    wl = sample_topology_batch(n_envs, n_flows, n_links=N_LINKS,
                               arrival_families=("poisson_arrivals",),
                               pad_flows=True, seed=seed, horizon=HORIZON)
    flows = stack_flow_schedules([
        arrival_schedule("poisson_arrivals", n_flows, horizon=HORIZON,
                         seed=seed * 7919 + i, hold_frac=HOLD_FRAC)
        for i in range(n_envs)])
    return wl.replace(flows=pad_flow_schedule(flows, flow_bucket(n_flows)),
                      specs=None)


def active_bound(wl, duration):
    """max_active covering the drawn workload's peak concurrency over any
    one step, so the sparse solve drops no flow."""
    from repro.core.fleet import flow_bucket, max_concurrent_flows
    peak = max_concurrent_flows(wl.flows, window=duration)
    bound = flow_bucket(peak)
    assert peak <= bound < wl.flows.n_flows, (peak, bound)
    return peak, bound


def topology_cfg(n_envs, n_flows, bound, *, backend="jnp", rounds=ROUNDS):
    from repro.core.ppo import PPOConfig
    from repro.core.simulator import TOPOLOGY_OBS
    return PPOConfig(n_envs=n_envs, n_flows=n_flows, obs_spec=TOPOLOGY_OBS,
                     pad_flows=True, max_active=bound, backend=backend,
                     max_episodes=rounds * n_envs)


def compile_episode(p, cfg, wl=None):
    """AOT-compile the trainer's episode program for the arguments
    ``train_ppo`` passes it; returns (compiled, seconds)."""
    import jax
    from repro.core.ppo import _broadcast_table, _make_episode_fn, init_agent
    from repro.core.schedule import constant_table
    key = jax.random.PRNGKey(cfg.seed)
    state = init_agent(key, cfg)
    if wl is None:
        fn = _make_episode_fn(p, cfg, randomize_t0=False)
        tables = _broadcast_table(constant_table(p.tpt, p.bw, p.duration),
                                  cfg.n_envs)
        args = (state, tables, None, None, None, key)
    else:
        fn = _make_episode_fn(p, cfg, randomize_t0=True, topology=True)
        args = (state, None, wl.flows, wl.objectives, wl.topology, key)
    t = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t


def train(p, cfg, wl=None, mesh=None):
    """train_ppo with per-round wall times (round 0 includes compiling).
    Every round reruns the same workload."""
    from repro.core.ppo import train_ppo
    marks = [time.perf_counter()]

    def same_workload(rnd):
        marks.append(time.perf_counter())
        return wl

    res = train_ppo(p, cfg, workload=wl, mesh=mesh,
                    resample=None if wl is None else same_workload)
    marks.append(time.perf_counter())
    hist = np.asarray(res.history)
    assert hist.shape == (cfg.max_episodes,), hist.shape
    assert np.isfinite(hist).all(), hist
    return res, hist, np.diff(marks)


def round_note(tag, secs, hist, n_envs):
    means = [float(hist[i * n_envs:(i + 1) * n_envs].mean())
             for i in range(len(hist) // n_envs)]
    note(f"[{tag}] round wall s {[round(float(s), 3) for s in secs]} "
         f"(round 0 includes compile); mean episode reward per round "
         f"{[round(m, 4) for m in means]}")


# ---------------------------------------------------------------------------
# Phase A: topology fleet training, the main training path
# ---------------------------------------------------------------------------

def check_sparse_matches_dense(p):
    """The sparse active-set topology solve against the dense reference, on
    a small fleet, on the device: equal to float32 ulp noise."""
    import jax
    import jax.numpy as jnp
    from repro.core.topology import topology_interval
    wl = topology_workload(1, 16, seed=3)
    graph, paths, flows, obj = jax.tree_util.tree_map(
        lambda x: x[0], (wl.topology.graph, wl.topology.paths, wl.flows,
                         wl.objectives))
    threads = jnp.asarray(np.random.default_rng(0).integers(1, 30, (16, 3)),
                          jnp.float32)
    _, bound = active_bound(wl, float(p.duration))
    worst = 0.0
    for t0 in np.arange(0.0, HORIZON, 2.0):
        kw = dict(graph=graph, paths=paths, flows=flows, objectives=obj)
        b_d, tps_d = topology_interval(p, jnp.zeros((16, 2)), threads, t0,
                                       **kw)
        b_s, tps_s = topology_interval(p, jnp.zeros((16, 2)), threads, t0,
                                       max_active=bound, **kw)
        np.testing.assert_allclose(np.asarray(tps_s), np.asarray(tps_d),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(b_s), np.asarray(b_d),
                                   atol=1e-6)
        worst = max(worst, float(np.abs(np.asarray(tps_s)
                                        - np.asarray(tps_d)).max()))
    note(f"[A] sparse vs dense topology solve on device (F=16, E=8, "
         f"A={bound}): max |dtps| {worst:.3e} (pinned 1e-6)")


def phase_a(p, device, n_envs=16, n_flows=4096):
    wl = topology_workload(n_envs, n_flows)
    peak, bound = active_bound(wl, float(p.duration))
    note(f"[A] workload: n_envs={n_envs} F={n_flows} E={N_LINKS} "
         f"peak concurrency {peak} -> max_active A={bound}")
    cfg = topology_cfg(n_envs, n_flows, bound)
    compiled, secs = compile_episode(p, cfg, wl)
    note(f"[A] episode compile {secs:.2f}s; {compiled.memory_analysis()}")
    res, hist, rounds = train(p, cfg, wl)
    round_note("A", rounds, hist, n_envs)
    note(f"[A] peak_bytes_in_use {peak_bytes(device)}")
    check_sparse_matches_dense(p)
    return wl, cfg, res, hist


# ---------------------------------------------------------------------------
# Phase B: the single-flow trainer on both backends; the kernels compiled
# ---------------------------------------------------------------------------

def check_sim_interval_backends(n_envs):
    """One batched sim_interval, jnp scan vs compiled Pallas kernel, at the
    1e-5 the interpret-mode tests pin."""
    import jax
    import jax.numpy as jnp
    from repro.core.simulator import make_env_params, sim_interval
    from repro.scenarios import sample_scenario_batch
    p = make_env_params(tpt=[0.2, 0.05, 0.2], bw=[2, 2, 2], cap=[0.5, 0.5],
                        n_max=50)
    _, tables = sample_scenario_batch(n_envs, seed=7, horizon=20.0)
    rng = np.random.default_rng(1)
    threads = jnp.asarray(rng.integers(1, 30, (n_envs, 3)), jnp.float32)
    bufs = jnp.asarray(rng.uniform(0.0, 0.5, (n_envs, 2)), jnp.float32)
    t0 = jnp.asarray(rng.uniform(0.0, 15.0, n_envs), jnp.float32)
    out = {}
    for backend in ("jnp", "pallas"):
        step = jax.jit(jax.vmap(
            lambda tab, b, th, t, backend=backend: sim_interval(
                p, b, th, t, table=tab, backend=backend)))
        if backend == "pallas":
            assert compiled_kernel_in(step.lower(
                tables, bufs, threads, t0).compile().as_text(),
                "sim_step_sched")
        out[backend] = [np.asarray(x) for x in step(tables, bufs, threads,
                                                    t0)]
    for a, b in zip(out["jnp"], out["pallas"]):
        np.testing.assert_allclose(b, a, atol=1e-5)
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(out["jnp"], out["pallas"]))
    note(f"[B] sim_interval jnp vs pallas (compiled), {n_envs} envs: "
         f"max |diff| {diff:.3e} (pinned 1e-5)")


def check_contention_kernel(n_flows, n_links, substeps=50):
    """The compiled contention kernel against the jnp reference solve at the
    topology widths: caps, floors and n_flows water-fill rounds."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.contention.ops import contention_rates
    from repro.kernels.contention.ref import contention_rates_reference
    rng = np.random.default_rng(0)
    F, E, S = n_flows, n_links, substeps
    threads = jnp.asarray(rng.integers(1, 30, (F, 3)), jnp.float32)
    act = jnp.asarray(rng.random((S, F)) < 0.5, jnp.float32)
    onpath = jnp.asarray(rng.random((S, F, E)) < 0.4, jnp.float32)
    tpt = jnp.asarray(rng.uniform(0.02, 0.5, (S, E, 3)), jnp.float32)
    bw = jnp.asarray(rng.uniform(0.1, 2.0, (S, E, 3)), jnp.float32)
    floor = jnp.asarray(rng.uniform(0.0, 0.02, F), jnp.float32)
    cap = jnp.asarray(np.where(rng.random(F) < 0.5, np.inf,
                               rng.uniform(0.005, 0.05, F)), jnp.float32)
    args = (threads, act, onpath, tpt, bw, floor, cap)
    text = contention_rates.lower(*args, rounds=F).compile().as_text()
    assert compiled_kernel_in(text, "contention_solve")
    got = np.asarray(contention_rates(*args, rounds=F))
    want = np.asarray(jax.jit(contention_rates_reference,
                              static_argnames="rounds")(*args, rounds=F))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    note(f"[B] contention kernel (compiled) vs jnp solve, F={F} E={E} "
         f"S={S} rounds={F}: max |diff| "
         f"{float(np.abs(got - want).max()):.3e} (pinned 1e-4)")


def phase_b(device, wl, topo_cfg, topo_hist, n_envs=1024):
    from dataclasses import replace
    from repro.core.ppo import PPOConfig
    from repro.core.simulator import make_env_params
    # the paper's read-bottleneck single-flow world
    p = make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                        n_max=50)
    first = {}
    for backend in ("jnp", "pallas"):
        cfg = PPOConfig(n_envs=n_envs, max_episodes=ROUNDS * n_envs,
                        backend=backend)
        compiled, secs = compile_episode(p, cfg)
        if backend == "pallas":
            assert compiled_kernel_in(compiled.as_text(), "sim_step_sched")
        note(f"[B] single-flow {backend} episode, n_envs={n_envs}: "
             f"compile {secs:.2f}s")
        res, hist, _ = train(p, cfg)
        note(f"[B] single-flow {backend}: {ROUNDS} rounds in "
             f"{res.wall_s:.3f}s (first round includes compile), best "
             f"episode reward {res.best_reward:.4f}")
        first[backend] = hist[:n_envs]
    note(f"[B] first-round rewards jnp vs pallas: max |diff| "
         f"{float(np.abs(first['jnp'] - first['pallas']).max()):.3e}")
    check_sim_interval_backends(n_envs)
    check_contention_kernel(topo_cfg.max_active, N_LINKS)
    # the fleet path on the fused kernels: phase A's workload, pallas
    p_topo = env_params()
    cfg = replace(topo_cfg, backend="pallas", max_episodes=topo_cfg.n_envs)
    compiled, secs = compile_episode(p_topo, cfg, wl)
    text = compiled.as_text()
    assert compiled_kernel_in(text, "contention_solve")
    assert compiled_kernel_in(text, "sim_step_sched")
    note(f"[B] topology pallas episode compile {secs:.2f}s; "
         f"{compiled.memory_analysis()}")
    _, hist, rounds = train(p_topo, cfg, wl)
    round_note("B topology pallas", rounds, hist, cfg.n_envs)
    note(f"[B] topology first-round rewards jnp vs pallas: max |diff| "
         f"{float(np.abs(hist - topo_hist[:cfg.n_envs]).max()):.3e}")
    note(f"[B] peak_bytes_in_use {peak_bytes(device)}")


# ---------------------------------------------------------------------------
# Phase C: live control with phase A's policy
# ---------------------------------------------------------------------------

def _policy_mean_np(params, x):
    """float64 NumPy reference of ``networks.policy_apply``'s mean head."""
    import jax
    P = jax.tree_util.tree_map(lambda v: np.asarray(v, np.float64), params)

    def linear(q, h):
        return h @ q["w"] + q["b"]

    def layernorm(q, h):
        mu = h.mean(-1, keepdims=True)
        var = h.var(-1, keepdims=True)
        return (h - mu) / np.sqrt(var + 1e-5) * q["scale"] + q["bias"]

    h = np.tanh(linear(P["embed"], np.asarray(x, np.float64)))
    for b in ("b0", "b1", "b2"):
        q = P[b]
        g = np.maximum(layernorm(q["ln1"], linear(q["l1"], h)), 0.0)
        h = h + np.maximum(layernorm(q["ln2"], linear(q["l2"], g)), 0.0)
    raw = linear(P["mean"], np.tanh(h)) + P["mean_bias_units"]
    return raw * P["action_scale"]


def phase_c(policy_params, obs_spec, n_live=16, n_flows=4096, n_max=8):
    from repro.core.controller import TopologyController
    from repro.transfer import NullSink, SharedLink, SyntheticSource
    link_bps = 64 * MB
    link = SharedLink(aggregate_bps=(None, link_bps, None))
    for f in range(n_live):
        link.attach(SyntheticSource(1 << 40, chunk_bytes=64 * 1024, seed=f),
                    NullSink(), initial_concurrency=(2, 2, 2), n_max=n_max,
                    metric_interval=0.2)
    ctrl = TopologyController(policy_params, n_flows=n_live, n_max=n_max,
                              bw_ref=link_bps, obs_spec=obs_spec,
                              interval=0.5, paths=np.ones((n_live, 1)),
                              link_bw_ref=[link_bps])
    t = time.perf_counter()
    try:
        trace = ctrl.run(link, interval=0.5, max_steps=5)
        moved = link.bytes_written()
    finally:
        link.close()
    wall = time.perf_counter() - t
    assert len(trace) == 5, len(trace)
    for _, threads, _ in trace:
        assert all(1 <= n <= n_max for n3 in threads for n in n3), threads
    assert moved > 0
    note(f"[C] live: {n_live} engines on a SharedLink, {len(trace)} "
         f"intervals of 0.5s in {wall:.2f}s (interval ends at "
         f"{[round(t, 3) for t, _, _ in trace]} s; the first includes "
         f"compiling the act step), {moved / MB:.1f} MB moved, "
         f"{ctrl.fleet_policy.n_dispatch} policy dispatches")

    # one policy step for a 4096-flow batched observation
    rng = np.random.default_rng(0)
    onpath = (rng.random((n_flows, N_LINKS)) < 0.3).astype(float)
    ctrl = TopologyController(policy_params, n_flows=n_flows, n_max=n_max,
                              bw_ref=1.0, obs_spec=obs_spec,
                              paths=onpath, link_bw_ref=np.ones(N_LINKS))
    obs = dict(threads=rng.integers(1, n_max + 1, (n_flows, 3)).astype(float),
               throughputs=rng.uniform(0.0, 0.05, (n_flows, 3)),
               sender_free=rng.uniform(0.1, 2.0, n_flows),
               receiver_free=rng.uniform(0.1, 2.0, n_flows),
               sender_capacity=np.full(n_flows, 2.0),
               receiver_capacity=np.full(n_flows, 2.0))
    active = (rng.random(n_flows) < 0.1).astype(float)
    lat = []
    for _ in range(20):
        t = time.perf_counter()
        acts = ctrl.step_arrays(obs, active)
        lat.append(time.perf_counter() - t)
    assert acts.shape == (n_flows, 3)
    # the policy's PRNG key is an output of the jitted act step: it lives
    # where the dispatch ran
    platform = next(iter(ctrl.fleet_policy._key.devices())).platform
    assert platform == PLATFORM, platform
    frames = ctrl.frames_arrays(obs, active)
    want = np.clip(np.round(_policy_mean_np(policy_params, frames)),
                   1, n_max)
    assert np.abs(acts - want).max() <= 1, np.abs(acts - want).max()
    note(f"[C] step_arrays F={n_flows}: dispatch on {platform}, "
         f"{float(np.mean(acts == want)):.4f} of actions equal to the "
         f"float64 reference (all within 1 thread); host wall per step "
         f"p50 {np.percentile(lat[1:], 50) * 1e3:.2f} ms, "
         f"first {lat[0] * 1e3:.1f} ms")


# ---------------------------------------------------------------------------
# --four-chips: flow-sharded fleet training
# ---------------------------------------------------------------------------

def four_chips(devices, n_flows=4096, n_envs_a=16, n_envs_b=32):
    """(a) phase A's workload sharded 4 ways against one chip; (b) the
    largest env batch that fits: sharding the flow axis does not shrink the
    per-chip footprint in an AOT compile for a v5e (7.5 GB at n_envs=16 on
    one chip or four), so n_envs=64 needs 26.6 GB per chip and 32 is
    taken."""
    from repro.launch.mesh import make_fleet_mesh
    from repro.sharding.fleet import shard_flow_schedule, shard_path_spec
    assert len(devices) >= 4, devices
    assert len({d.id for d in devices[:4]}) == 4
    assert all(d.platform == PLATFORM for d in devices[:4]), devices
    mesh = make_fleet_mesh(4)
    p = env_params()

    # (a) phase A's workload, sharded over 4 chips vs unsharded on one
    wl = topology_workload(n_envs_a, n_flows)
    _, bound = active_bound(wl, float(p.duration))
    for x in (shard_flow_schedule(wl.flows, mesh).t_start,
              shard_path_spec(wl.topology.paths, mesh).onpath):
        assert len(x.sharding.device_set) == 4, x.sharding
        assert x.addressable_shards[0].data.shape[-1 if x.ndim == 2
                                                  else -2] \
            == n_flows // 4, x.sharding
    cfg = topology_cfg(n_envs_a, n_flows, bound, rounds=1)
    _, one, secs1 = train(p, cfg, wl)
    _, four, secs4 = train(p, cfg, wl, mesh=mesh)
    np.testing.assert_allclose(four, one, rtol=1e-5, atol=1e-5)
    note(f"[4] (a) n_envs={n_envs_a} F={n_flows} A={bound}: first-round rewards "
         f"4-way sharded vs one chip, max |diff| "
         f"{float(np.abs(four - one).max()):.3e} (pinned 1e-5); round wall "
         f"s one chip {secs1[0]:.2f}, sharded {secs4[0]:.2f} (with "
         f"compile)")

    # (b) the largest batch per chip, flow-sharded
    wl = topology_workload(n_envs_b, n_flows, seed=1)
    peak, bound = active_bound(wl, float(p.duration))
    cfg = topology_cfg(n_envs_b, n_flows, bound)
    _, hist, rounds = train(p, cfg, wl, mesh=mesh)
    round_note(f"4 (b) n_envs={n_envs_b} F={n_flows} peak {peak} A={bound}",
               rounds, hist, n_envs_b)
    note("[4] peak_bytes_in_use per chip "
         f"{[peak_bytes(d) for d in devices[:4]]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the flow-sharded fleet trainer on 4 "
                         "chips and its one-chip comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        sys.exit(f"chip_smoke: needs a TPU, found {devices[0].platform!r}")
    from repro.launch.compile_cache import enable_compile_cache
    note(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    note(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"jax {jax.__version__}")

    if args.four_chips:
        four_chips(devices)
    else:
        p = env_params()
        t = time.perf_counter()
        wl, cfg, res, hist = phase_a(p, dev)
        note(f"[A] done in {time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        phase_b(dev, wl, cfg, hist)
        note(f"[B] done in {time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        phase_c(res.params["policy"], cfg.obs_spec)
        note(f"[C] done in {time.perf_counter() - t:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
