"""§V-A: offline training time.

Paper numbers: ~45 min average offline (their Python event-sim), ~20150
episodes to convergence, vs ~7 days online (3 s per iteration on the wire,
x10 iterations x episodes), wasting ~5.6 PB at 100 Gbps.

Here: the vectorized JAX simulator trains the same Algorithm-2 agent in
seconds; we report measured wall time, episodes, and the projected
online-training equivalents computed with the paper's own constants.
"""

from __future__ import annotations

import time

from benchmarks.common import make_scenario_env, train_agent


def backend_rows(rows, *, n_envs=64, iters=20):
    """Inner dense-substep loop, jnp lax.scan vs the Pallas sim_step kernel,
    on the batched scenario-stepping path the trainer actually runs. On a
    CPU host the Pallas numbers are interpret-mode (correctness/overhead
    reference); on a TPU they are the compiled kernel."""
    import jax
    import jax.numpy as jnp
    from repro.core.simulator import make_env_params, env_reset, env_step
    from repro.scenarios import sample_scenario_batch

    p = make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1], cap=[2, 2],
                        n_max=50)
    _, tables = sample_scenario_batch(n_envs, seed=0, horizon=60.0)
    keys = jax.random.split(jax.random.PRNGKey(0), n_envs)
    acts = jnp.full((n_envs, 3), 8.0)
    per_backend = {}
    for backend in ("jnp", "pallas"):
        step = jax.jit(jax.vmap(
            lambda tab, st, a: env_step(p, st, a, table=tab,
                                        backend=backend)[0]))
        states = jax.vmap(
            lambda tab, k: env_reset(p, k, table=tab, backend=backend)
        )(tables, keys)
        st = step(tables, states, acts)
        jax.block_until_ready(st)  # compile outside the timed loop
        t0 = time.perf_counter()
        for _ in range(iters):
            st = step(tables, st, acts)
        jax.block_until_ready(st)
        per = (time.perf_counter() - t0) / iters
        per_backend[backend] = per
        rows.append((f"training_time.sim_backend_{backend}_us",
                     per * 1e6,
                     f"{per * 1e3:.2f} ms per batched env step "
                     f"({n_envs} envs, backend={backend}, "
                     f"{jax.default_backend()} host)"))
    ratio = per_backend["pallas"] / max(per_backend["jnp"], 1e-12)
    rows.append(("training_time.sim_backend_pallas_vs_jnp", ratio * 1e6,
                 f"{ratio:.2f}x (interpret-mode emulation off-TPU)"))
    return rows


def policy_rows(rows, *, n_envs=16, iters=8):
    """Per-policy cost of one jitted episode batch (rollout + ppo_epochs
    updates): what the temporal stack costs over the feed-forward baseline —
    "stacked" widens the input, "gru" threads a carry through the episode
    scan AND replays it per update epoch (truncated BPTT)."""
    import jax
    from repro.core.ppo import (PPOConfig, _make_episode_fn, init_agent,
                                _broadcast_table)
    from repro.core.schedule import constant_table
    from repro.core.simulator import make_env_params, CONTEXT_OBS

    p = make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1], cap=[2, 2],
                        n_max=50)
    tables = _broadcast_table(constant_table(p.tpt, p.bw, p.duration), n_envs)
    per_policy = {}
    for policy in ("mlp", "stacked", "gru"):
        cfg = PPOConfig(n_envs=n_envs, obs_spec=CONTEXT_OBS, policy=policy)
        key = jax.random.PRNGKey(0)
        state = init_agent(key, cfg)
        episode = _make_episode_fn(p, cfg, randomize_t0=False)
        # flows/objectives/topo None: the single-flow episode path
        state, _, _ = episode(state, tables, None, None, None, key)  # compile
        jax.block_until_ready(state["params"])
        t0 = time.perf_counter()
        for _ in range(iters):
            state, _, _ = episode(state, tables, None, None, None, key)
        jax.block_until_ready(state["params"])
        per = (time.perf_counter() - t0) / iters
        per_policy[policy] = per
        rows.append((f"training_time.episode_{policy}_us", per * 1e6,
                     f"{per * 1e3:.2f} ms per episode batch "
                     f"({n_envs} envs, policy={policy})"))
    ratio = per_policy["gru"] / max(per_policy["mlp"], 1e-12)
    rows.append(("training_time.episode_gru_vs_mlp", ratio * 1e6,
                 f"{ratio:.2f}x recurrent episode cost over mlp"))
    return rows


def fleet_scaling_rows(rows, *, Fs=(1, 8, 64, 512, 4096), iters=5,
                       substeps=50, pallas_max_f=None):
    """Fleet scale-out: cost of one jitted ``fleet_step`` at F flows, dense
    reference vs the sparse compact-active-set solve vs the fused Pallas
    contention kernel (sparse gather feeding the kernel). The arrival
    schedule is a Poisson process with short hold windows — Globus-style
    sparse instantaneous activity, where thousands of flows exist but only
    a few hundred are live in any one step — so ``max_active`` (sized by
    ``max_concurrent_flows`` + ``flow_bucket``) is far below F and the
    sparse path's advantage is structural, not a microbenchmark artifact.
    Off-TPU the pallas rows run the kernel in interpret mode (correctness
    reference, NOT representative of compiled TPU cost), so
    ``pallas_max_f`` caps how far up the F grid they go (None = all)."""
    import jax
    import jax.numpy as jnp
    from repro.core.fleet import (FlowSchedule, fleet_step, flow_bucket,
                                  max_concurrent_flows)
    from repro.core.simulator import make_env_params
    from repro.scenarios.families import poisson_arrivals

    p = make_env_params(tpt=[0.2, 0.15, 0.2], bw=[1, 1, 1], cap=[2, 2],
                        n_max=50)
    per = {}
    for F in Fs:
        ts, te = poisson_arrivals(F, 60.0, seed=7, hold_frac=0.01)
        flows = FlowSchedule(t_start=jnp.asarray(ts), t_end=jnp.asarray(te))
        A = min(flow_bucket(max_concurrent_flows(flows, window=p.duration)),
                F)
        variants = [("dense", "jnp", None), ("sparse", "jnp", A)]
        if pallas_max_f is None or F <= pallas_max_f:
            variants.append(("pallas", "pallas", A))
        from repro.core.fleet import FleetState
        state = FleetState(
            buffers=jnp.zeros((F, 2), jnp.float32),
            threads=jnp.full((F, 3), 8.0),
            throughputs=jnp.zeros((F, 3), jnp.float32),
            t=jnp.float32(0.0),
            prev_throughputs=jnp.zeros((F, 3), jnp.float32),
            delivered=jnp.zeros((F,), jnp.float32))
        acts = jnp.full((F, 3), 8.0)
        for name, backend, ma in variants:
            # two warm-up calls: the first compiles, the second warms the
            # returned-state signature (its scalar clock is strong-typed
            # where the hand-built one is weak) so the timed loop never
            # retraces
            st = fleet_step(p, state, acts, flows=flows, substeps=substeps,
                            backend=backend, max_active=ma)[0]
            st = fleet_step(p, st, acts, flows=flows, substeps=substeps,
                            backend=backend, max_active=ma)[0]
            jax.block_until_ready(st)
            t0 = time.perf_counter()
            for _ in range(iters):
                st = fleet_step(p, st, acts, flows=flows, substeps=substeps,
                                backend=backend, max_active=ma)[0]
            jax.block_until_ready(st)
            dt = (time.perf_counter() - t0) / iters
            per[(F, name)] = dt
            note = f"A={ma}" if ma is not None else "full F"
            if name == "pallas" and jax.default_backend() != "tpu":
                note += ", interpret-mode"
            rows.append((f"training_time.fleet_step_F{F}_{name}_us",
                         dt * 1e6,
                         f"{dt * 1e3:.2f} ms per fleet_step "
                         f"(F={F}, {note})"))
        if (F, "sparse") in per:
            ratio = per[(F, "dense")] / max(per[(F, "sparse")], 1e-12)
            rows.append((f"training_time.fleet_sparse_speedup_F{F}",
                         ratio * 1e6,
                         f"{ratio:.1f}x sparse over dense at F={F}"))
    return rows


def main(rows=None):
    rows = rows if rows is not None else []
    p = make_scenario_env("read")
    t0 = time.time()
    ctrl, res, ex = train_agent(p, seed=0, episodes=30000)
    wall = time.time() - t0
    online_s = res.episodes * 10 * 3  # 10 iters/episode, 3 s per config probe
    online_pb = online_s * 12.5 / 1e6  # 100 Gbps = 12.5 GB/s -> PB
    rows += [
        ("training_time.offline_wall_s", wall * 1e6, f"{wall:.1f}s"),
        ("training_time.episodes", res.episodes,
         f"converged_at={res.converged_at}"),
        ("training_time.best_reward_frac_rmax",
         res.best_reward / (ex.r_max * 10) * 1e6,
         f"{res.best_reward / (ex.r_max * 10):.3f}"),
        ("training_time.online_equiv_s", online_s * 1e6,
         f"{online_s / 86400:.2f} days online (paper: ~5-7 days)"),
        ("training_time.online_equiv_PB", online_pb * 1e6,
         f"{online_pb:.2f} PB at 100 Gbps (paper: ~5.62 PB)"),
        ("training_time.speedup_vs_paper_45min",
         (45 * 60 / max(wall, 1e-9)) * 1e6,
         f"{45 * 60 / max(wall, 1e-9):.0f}x vs paper's 45 min"),
    ]
    backend_rows(rows)
    policy_rows(rows)
    return rows


if __name__ == "__main__":
    for r in main():
        print(",".join(str(x) for x in r))
