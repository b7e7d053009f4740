"""Algorithm 2: PPO training for thread allocation — one schedule-native
trainer.

Faithful loop structure: N episodes, each = reset to random threads + M env
steps + ONE batched update over the episode memory (clipped surrogate +
0.5*MSE critic - 0.1*entropy, Adam), old policy refreshed after each episode,
convergence when best episode reward reaches 0.9*R_max and then ``patience``
episodes pass without improvement.

``train_ppo`` covers every training regime through ONE jitted episode fn:

  static          train_ppo(params, cfg) — no workload; the env runs the
                  params' frozen conditions as a 1-bin schedule
  single schedule train_ppo(params, cfg, workload=Workload(tables=...))
  domain random.  train_ppo(params, cfg, workload=..., resample=fn) — the
                  batched schedule tables are a TRACED argument, so redrawing
                  the scenario distribution between episode batches reuses
                  the one compiled program (no per-schedule retrace)

The ``Workload`` bundle (repro.core.workload) carries every scenario axis —
tables, flow arrivals, per-flow objectives, topology, and fault schedules —
and ``resample=fn(round) -> Workload`` redraws them together; the samplers
in repro.scenarios return it directly. The per-axis kwarg pairs below are
deprecated shims for one cycle.

Beyond-paper: the rollout is vmapped over ``cfg.n_envs`` parallel simulator
environments and the whole episode+update is one jitted call — this is what
makes offline training take seconds here vs the paper's 45 minutes (their
simulator is a Python heap, popping one event at a time; ours advances every
environment one dense interval per fused step). ``cfg.obs_spec`` selects the
observation (schedule context on/off; the network widths follow spec.dim),
``cfg.policy`` the temporal policy ("mlp" | "stacked" frame-stacking |
"gru" recurrent carry), and ``cfg.backend`` the inner substep-loop
implementation ("jnp" | "pallas").

Fleet training (``cfg.n_flows > 1``): ONE shared policy is applied to every
flow's observation row (the networks broadcast over the F axis — no extra
parameters), the env is the contention model of :mod:`repro.core.fleet`,
and the per-step reward is shared across the fleet: aggregate utility +
``cfg.fairness_coef`` * Jain's index over active flows' goodput. Each
(step, flow) pair becomes one PPO sample against the shared return —
flows join/leave mid-episode via ``flows=``/``resample_flows=`` (batched
``FlowSchedule``, the arrival twin of ``tables=``/``resample=``).
``n_flows=1`` is the single-flow trainer, bit-for-bit.

Heterogeneous objectives (``objectives=``/``resample_objectives=``, batched
``FlowObjective``): each flow carries a priority weight, optional deadline,
and optional rate floor/cap — the reward becomes Σ weight_f·utility_f −
``cfg.deadline_coef``·Σ weight_f·miss_penalty_f + ``cfg.fairness_coef``·
weighted-Jain, and ``ObservationSpec(objectives=True)`` exposes each flow's
priority/slack/urgency so ONE shared policy learns to starve bronze flows
to save a gold deadline. ``objectives=None`` is the objective-free fleet,
bit-for-bit.
"""

from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, replace as dc_replace
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import networks as nets
from repro.core.workload import Workload
from repro.core.fleet import (fleet_reset, fleet_step, fleet_observe,
                              always_on, flow_bucket, pad_flow_schedule,
                              pad_flow_objectives)
from repro.core.topology import (topology_reset, topology_step,
                                 topology_observe, Topology, pad_path_spec)
from repro.core.tracing import mark, span
from repro.core.schedule import constant_table
from repro.core.simulator import (env_reset, env_step, observe, ACT_DIM,
                                  ObservationSpec, DEFAULT_OBS,
                                  history_init, history_push, history_flatten)
from repro.optim import adamw_init, adamw_update

POLICIES = ("mlp", "stacked", "gru")


@dataclass
class PPOConfig:
    max_steps: int = 10          # M — steps per episode
    max_episodes: int = 30000    # N
    lr: float = 3e-4
    gamma: float = 0.99
    gae_lambda: float = 1.0      # GAE(lambda) advantage estimation: 1.0 is
    # plain discounted Monte-Carlo returns (the paper's estimator — kept as
    # a STATIC branch so the default stays bit-identical to the pre-GAE
    # trainer, pinned in tests/test_gae.py); < 1.0 bootstraps on the critic
    # (from the PRE-update params — a fixed baseline across the ppo_epochs)
    # for lower-variance credit assignment on slow-trending and failover
    # schedules, where a 10-step Monte-Carlo return is mostly scenario luck.
    clip_eps: float = 0.2
    entropy_coef: float = 0.1
    critic_coef: float = 0.5
    ppo_epochs: int = 4
    normalize_adv: bool = True
    n_envs: int = 1              # 1 = paper-faithful sequential episodes
    substeps: int = 50
    patience: int = 1000
    convergence_frac: float = 0.9
    action_scale: float = 25.0
    init_log_std: float = 1.5
    max_grad_norm: float = 0.5
    seed: int = 0
    log_every: int = 0
    obs_spec: ObservationSpec = DEFAULT_OBS  # observation layout (spec.dim)
    policy: str = "mlp"          # "mlp" | "stacked" | "gru" (temporal stack):
    # "stacked" frame-stacks the last ``history`` observations (HistorySpec;
    # zero-padded reset) into a feed-forward input; "gru" threads a recurrent
    # carry through the episode scan (truncated BPTT over the M-step
    # episode). A 1-frame "stacked"/"mlp" policy is bit-identical to the
    # plain path (pinned in tests/test_temporal_policies.py).
    history: int = 4             # frames stacked when policy="stacked"
    rnn_hidden: int = 64         # GRU carry width when policy="gru"
    backend: str = "jnp"         # inner substep loop: "jnp" | "pallas"
    n_flows: int = 1             # >1: fleet training — ONE shared policy
    # stepped per-flow through the repro.core.fleet contention model (the
    # scheduled stage capacity splits across active flows in proportion to
    # their thread counts); obs_spec usually adds the cross-flow features
    # (ObservationSpec(fleet=True) / FLEET_OBS). n_flows=1 is the
    # single-flow trainer, bit-for-bit.
    fairness_coef: float = 0.0   # weight of the Jain's-fairness reward term
    # (fleet only): reward = sum_f utility_f + fairness_coef * Jain(active
    # flows' goodput) — pushes the shared policy toward an even split of the
    # bottleneck instead of starving late arrivals. With per-flow
    # objectives the Jain term is priority-weighted (goodput_f / weight_f).
    deadline_coef: float = 1.0   # weight of the smooth deadline-miss
    # penalty (fleet only, traced): how hard the shared policy is punished
    # for letting a deadline flow's goodput fall below the rate it still
    # needs. Irrelevant without objectives — the penalty is masked to
    # exactly 0.0 for flows with no finite deadline+demand, which keeps the
    # objective-free path bit-identical.
    max_active: int | None = None  # fleet scale-out: static bound on how
    # many flows can be active in any one step interval — the contention
    # solve gathers that compact set, contends it, and scatters back
    # (bitwise-equal to the dense solve), so episode cost scales with the
    # bound instead of n_flows. Size it with repro.core.fleet.
    # max_concurrent_flows(flows, window=duration) rounded up by
    # flow_bucket; None = the dense solve. A bound smaller than the true
    # peak concurrency silently drops the overflow — it is a promise.
    pad_flows: bool = False      # fleet scale-out: pad the fleet to the
    # next power-of-two bucket (flow_bucket(n_flows)) and pad every
    # resampled FlowSchedule/FlowObjective/PathSpec batch to match, so
    # sweeping flow counts stops retriggering XLA recompiles. Padded flows
    # are never active: they move nothing, score exactly zero utility, and
    # are masked from the Jain term — the reward is unchanged
    # (property-pinned in tests/test_fleet_scaleout.py).
    param_selection: str = "best_episode"  # | "batch_mean": under domain
    # randomization a single episode's reward mostly measures how lucky the
    # sampled scenario was; the mean over the whole randomized batch is a
    # far lower-variance estimate of policy quality, so best-params
    # selection (and the stagnation counter) can track it instead. History,
    # best_reward, and the paper's convergence criterion stay per-episode.


@dataclass
class TrainResult:
    params: dict
    episodes: int
    wall_s: float
    history: list
    converged_at: int | None
    best_reward: float
    r_max: float | None


def effective_obs_spec(cfg: PPOConfig) -> ObservationSpec:
    """The observation layout the POLICY actually consumes: policy="stacked"
    frame-stacks ``cfg.history`` frames onto ``cfg.obs_spec`` (unless the
    spec already carries an explicit history); "mlp"/"gru" take the spec as
    given. Network widths derive from this spec's ``dim``."""
    if cfg.policy == "stacked" and cfg.obs_spec.history == 1:
        return cfg.obs_spec._replace(history=cfg.history)
    return cfg.obs_spec


def init_agent(key, cfg: PPOConfig):
    if cfg.policy not in POLICIES:
        raise ValueError(f"unknown policy {cfg.policy!r}; expected one of "
                         f"{POLICIES}")
    kp, kv = jax.random.split(key)
    obs_dim = effective_obs_spec(cfg).dim
    if cfg.policy == "gru":
        params = {
            "policy": nets.rnn_policy_init(kp, obs_dim=obs_dim,
                                           act_dim=ACT_DIM,
                                           rnn_hidden=cfg.rnn_hidden,
                                           action_scale=cfg.action_scale,
                                           init_log_std=cfg.init_log_std),
            "value": nets.rnn_value_init(kv, obs_dim=obs_dim,
                                         rnn_hidden=cfg.rnn_hidden),
        }
    else:
        params = {
            "policy": nets.policy_init(kp, obs_dim=obs_dim, act_dim=ACT_DIM,
                                       action_scale=cfg.action_scale,
                                       init_log_std=cfg.init_log_std),
            "value": nets.value_init(kv, obs_dim=obs_dim),
        }
    return {"params": params, "opt": adamw_init(params)}


def _rollout(policy_params, env_params, table, key, *, M, substeps, spec,
             backend, randomize_t0, policy="mlp"):
    """One episode in one env under ``table``. When ``randomize_t0`` the
    episode start time is drawn uniformly over the schedule horizon so
    M-step episodes see every phase (domain randomization); static training
    keeps the paper's reset-at-zero and the paper's key stream.

    Temporal policies: the scan carry holds the (K, frame_dim) history
    window (zero-padded at reset; K=1 is exactly the unstacked path) and,
    for "gru", the recurrent carry (zeros at episode start — the same
    contract the loss replay and the live controller use). Returns per-step
    (obs, action, reward, logp) where obs is the stacked network input."""
    if randomize_t0:
        k_reset, k_t0, k_steps = jax.random.split(key, 3)
        horizon = table.tpt.shape[0] * table.bin_seconds
        span = jnp.maximum(horizon - (M + 1) * env_params.duration, 0.0)
        t0 = jax.random.uniform(k_t0, ()) * span
    else:
        k_reset, k_steps = jax.random.split(key)
        t0 = 0.0
    fspec = spec._replace(history=1)  # env-level spec: observe() is per-frame
    state = env_reset(env_params, k_reset, t0, table=table, substeps=substeps,
                      spec=fspec, backend=backend)
    obs0 = observe(env_params, state, table=table, spec=fspec)
    hist0 = history_init(spec, obs0)
    recurrent = policy == "gru"

    def step(carry, k):
        if recurrent:
            state, hist, h = carry
            obs = history_flatten(hist)
            h, mean, std = nets.rnn_policy_apply(policy_params, h, obs)
        else:
            state, hist = carry
            obs = history_flatten(hist)
            mean, std = nets.policy_apply(policy_params, obs)
        action = mean + std * jax.random.normal(k, mean.shape)
        logp = nets.gaussian_logp(mean, std, action)
        state, obs_next, reward = env_step(env_params, state, action,
                                           table=table, substeps=substeps,
                                           spec=fspec, backend=backend)
        hist = history_push(hist, obs_next)
        out = (state, hist, h) if recurrent else (state, hist)
        return out, (obs, action, reward, logp)

    init = ((state, hist0, nets.rnn_carry(policy_params)) if recurrent
            else (state, hist0))
    keys = jax.random.split(k_steps, M)
    _, traj = jax.lax.scan(step, init, keys)
    return traj  # obs (M,D), act (M,3), rew (M,), logp (M,)


def _rollout_fleet(policy_params, env_params, table, flows, objectives, key,
                   *, M, substeps, spec, backend, randomize_t0, policy,
                   n_flows, fairness_coef, deadline_coef, max_active=None):
    """One fleet episode: F flows contend for the scheduled capacity, ONE
    shared policy maps each flow's observation row to that flow's action
    (the networks broadcast over the F axis), and every step's reward is
    the shared fleet objective. History windows and the GRU carry get a
    leading flow axis; the per-flow contracts (zero-padded reset, zero
    carry) are unchanged, so fleet-trained params drop into the per-flow
    live controller. Returns per-step (obs (F, D), action (F, 3),
    reward (), logp (F,))."""
    if randomize_t0:
        k_reset, k_t0, k_steps = jax.random.split(key, 3)
        horizon = table.tpt.shape[0] * table.bin_seconds
        span = jnp.maximum(horizon - (M + 1) * env_params.duration, 0.0)
        t0 = jax.random.uniform(k_t0, ()) * span
    else:
        k_reset, k_steps = jax.random.split(key)
        t0 = 0.0
    fspec = spec._replace(history=1)
    state = fleet_reset(env_params, k_reset, n_flows, t0, flows=flows,
                        table=table, substeps=substeps, spec=fspec,
                        backend=backend, objectives=objectives,
                        max_active=max_active)
    obs0 = fleet_observe(env_params, state, flows=flows, table=table,
                         spec=fspec, objectives=objectives,
                         max_active=max_active)
    hist0 = jax.vmap(lambda f: history_init(spec, f))(obs0)  # (F, K, D)
    recurrent = policy == "gru"

    def step(carry, k):
        if recurrent:
            state, hist, h = carry
            obs = jax.vmap(history_flatten)(hist)
            h, mean, std = nets.rnn_policy_apply(policy_params, h, obs)
        else:
            state, hist = carry
            obs = jax.vmap(history_flatten)(hist)
            mean, std = nets.policy_apply(policy_params, obs)
        action = mean + std * jax.random.normal(k, mean.shape)
        logp = nets.gaussian_logp(mean, std, action)
        state, obs_next, reward = fleet_step(
            env_params, state, action, flows=flows, table=table,
            substeps=substeps, spec=fspec, backend=backend,
            fairness_coef=fairness_coef, objectives=objectives,
            deadline_coef=deadline_coef, max_active=max_active)
        hist = jax.vmap(history_push)(hist, obs_next)
        out = (state, hist, h) if recurrent else (state, hist)
        return out, (obs, action, reward, logp)

    init = ((state, hist0, nets.rnn_carry(policy_params, (n_flows,)))
            if recurrent else (state, hist0))
    keys = jax.random.split(k_steps, M)
    _, traj = jax.lax.scan(step, init, keys)
    return traj  # obs (M,F,D), act (M,F,3), rew (M,), logp (M,F)


def _rollout_topology(policy_params, env_params, topo, flows, objectives,
                      key, *, M, substeps, spec, backend, randomize_t0,
                      policy, n_flows, fairness_coef, deadline_coef,
                      max_active=None):
    """One topology episode: the fleet rollout's multi-link twin. Flows
    traverse the link paths of ``topo`` (a Topology bundle) and contend
    per-link via the work-conserving solve; the per-flow policy/history/
    carry contracts are exactly the fleet ones, so topology-trained params
    drop into the same live controller. Returns per-step (obs (F, D),
    action (F, 3), reward (), logp (F,))."""
    graph, paths = topo.graph, topo.paths
    if randomize_t0:
        k_reset, k_t0, k_steps = jax.random.split(key, 3)
        horizon = graph.tpt.shape[1] * graph.bin_seconds
        span = jnp.maximum(horizon - (M + 1) * env_params.duration, 0.0)
        t0 = jax.random.uniform(k_t0, ()) * span
    else:
        k_reset, k_steps = jax.random.split(key)
        t0 = 0.0
    fspec = spec._replace(history=1)
    state = topology_reset(env_params, k_reset, n_flows, t0, graph=graph,
                           paths=paths, flows=flows, substeps=substeps,
                           spec=fspec, backend=backend,
                           objectives=objectives, max_active=max_active)
    obs0 = topology_observe(env_params, state, graph=graph, paths=paths,
                            flows=flows, spec=fspec, objectives=objectives,
                            max_active=max_active)
    hist0 = jax.vmap(lambda f: history_init(spec, f))(obs0)  # (F, K, D)
    recurrent = policy == "gru"

    def step(carry, k):
        if recurrent:
            state, hist, h = carry
            obs = jax.vmap(history_flatten)(hist)
            h, mean, std = nets.rnn_policy_apply(policy_params, h, obs)
        else:
            state, hist = carry
            obs = jax.vmap(history_flatten)(hist)
            mean, std = nets.policy_apply(policy_params, obs)
        action = mean + std * jax.random.normal(k, mean.shape)
        logp = nets.gaussian_logp(mean, std, action)
        state, obs_next, reward = topology_step(
            env_params, state, action, graph=graph, paths=paths, flows=flows,
            substeps=substeps, spec=fspec, backend=backend,
            fairness_coef=fairness_coef, objectives=objectives,
            deadline_coef=deadline_coef, max_active=max_active)
        hist = jax.vmap(history_push)(hist, obs_next)
        out = (state, hist, h) if recurrent else (state, hist)
        return out, (obs, action, reward, logp)

    init = ((state, hist0, nets.rnn_carry(policy_params, (n_flows,)))
            if recurrent else (state, hist0))
    keys = jax.random.split(k_steps, M)
    _, traj = jax.lax.scan(step, init, keys)
    return traj  # obs (M,F,D), act (M,F,3), rew (M,), logp (M,F)


def _returns(rew, gamma):
    def back(g, r):
        g = r + gamma * g
        return g, g
    _, gs = jax.lax.scan(back, jnp.zeros(()), rew, reverse=True)
    return gs


def _gae_returns(rew, values, gamma, lam):
    """GAE(lambda) targets for ONE episode: advantage a_t = delta_t +
    gamma*lam*a_{t+1} with delta_t = r_t + gamma*V(s_{t+1}) - V(s_t) and
    V = 0 past the horizon, returned as a_t + V(s_t) (the lambda-return,
    drop-in for _returns as the critic target / advantage source). At
    lam=1 this telescopes to the discounted Monte-Carlo return for ANY
    values (property-pinned in tests/test_gae.py) — but only up to float
    associativity, which is why the trainer keeps lam==1.0 on a static
    _returns branch."""
    v_next = jnp.concatenate([values[1:], jnp.zeros_like(values[:1])])

    def back(a, xs):
        r, v, vn = xs
        a = (r + gamma * vn - v) + gamma * lam * a
        return a, a + v

    _, ret = jax.lax.scan(back, jnp.zeros(()), (rew, values, v_next),
                          reverse=True)
    return ret


def _surrogate(logp, logp_old, v, ret, entropy, cfg: PPOConfig):
    """Clipped PPO surrogate shared by the feed-forward and recurrent
    losses (inputs may be any matching shape; means are over all elems)."""
    adv = ret - jax.lax.stop_gradient(v)
    if cfg.normalize_adv:
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    ratio = jnp.exp(logp - logp_old)
    surr1 = ratio * adv
    surr2 = jnp.clip(ratio, 1.0 - cfg.clip_eps, 1.0 + cfg.clip_eps) * adv
    actor = -jnp.minimum(surr1, surr2).mean()
    critic = cfg.critic_coef * jnp.mean((ret - v) ** 2)
    entropy = entropy.mean()
    total = actor + critic - cfg.entropy_coef * entropy
    return total, {"actor": actor, "critic": critic, "entropy": entropy}


def _loss(params, batch, cfg: PPOConfig):
    obs, act, ret, logp_old = batch
    mean, std = nets.policy_apply(params["policy"], obs)
    logp = nets.gaussian_logp(mean, std, act)
    v = nets.value_apply(params["value"], obs)
    return _surrogate(logp, logp_old, v, ret, nets.gaussian_entropy(std), cfg)


def _loss_recurrent(params, batch, cfg: PPOConfig):
    """Recurrent PPO loss: replay the GRU over each episode SEQUENCE from
    the zero carry (truncated BPTT, truncation = the M-step episode) so the
    fresh params' logp/value reflect the carries THEY would have produced.
    ``batch`` keeps episode structure: obs (E,M,D), act (E,M,A), ret (E,M),
    logp_old (E,M)."""
    obs, act, ret, logp_old = batch

    def replay(obs_seq, act_seq):
        def stepfn(carry, xs):
            hp, hv = carry
            o, a = xs
            hp, mean, std = nets.rnn_policy_apply(params["policy"], hp, o)
            hv, v = nets.rnn_value_apply(params["value"], hv, o)
            return (hp, hv), (nets.gaussian_logp(mean, std, a), v,
                              nets.gaussian_entropy(std))

        carry0 = (nets.rnn_carry(params["policy"]),
                  nets.rnn_carry(params["value"]))
        _, (logp, v, ent) = jax.lax.scan(stepfn, carry0, (obs_seq, act_seq))
        return logp, v, ent

    logp, v, ent = jax.vmap(replay)(obs, act)  # (E, M) each
    return _surrogate(logp, logp_old, v, ret, ent, cfg)


def _make_episode_fn(env_params, cfg: PPOConfig, *, randomize_t0,
                     topology=False):
    """One jitted call = n_envs episodes + ppo_epochs updates — the single
    episode fn in the repo. ``tables`` (batched ScheduleTable, leading axis
    n_envs) and ``flows`` (batched FlowSchedule, fleet mode) are traced, so
    new schedule VALUES never retrace. ``topology`` (static flag) swaps the
    rollout for the multi-link twin: the ``topo`` arg (batched Topology,
    leading axis n_envs) replaces ``tables`` as the world, and the fleet
    batch shaping applies for any n_flows >= 1. The rollout and the
    ``ppo_epochs`` update run under the named scopes ``rollout`` and
    ``update``: op metadata only, the compiled numbers are the same."""
    spec = effective_obs_spec(cfg)
    recurrent = cfg.policy == "gru"
    fleet = cfg.n_flows > 1 and not topology
    multi = fleet or topology  # per-flow sample axis in the update batch
    loss_fn = _loss_recurrent if recurrent else _loss

    def episode(train_state, tables, flows, objectives, topo, key):
        params, opt = train_state["params"], train_state["opt"]
        k_roll, _ = jax.random.split(key)
        roll_keys = jax.random.split(k_roll, cfg.n_envs)
        with jax.named_scope("rollout"):
            if topology:
                obs, act, rew, logp = jax.vmap(
                    lambda tp, fl, ob, k: _rollout_topology(
                        params["policy"], env_params, tp, fl, ob, k,
                        M=cfg.max_steps, substeps=cfg.substeps, spec=spec,
                        backend=cfg.backend, randomize_t0=randomize_t0,
                        policy=cfg.policy, n_flows=cfg.n_flows,
                        fairness_coef=cfg.fairness_coef,
                        deadline_coef=cfg.deadline_coef,
                        max_active=cfg.max_active)
                )(topo, flows, objectives, roll_keys)
                # (E, M, F, ...) / rew (E, M)
            elif fleet:
                obs, act, rew, logp = jax.vmap(
                    lambda tab, fl, ob, k: _rollout_fleet(
                        params["policy"], env_params, tab, fl, ob, k,
                        M=cfg.max_steps, substeps=cfg.substeps, spec=spec,
                        backend=cfg.backend, randomize_t0=randomize_t0,
                        policy=cfg.policy, n_flows=cfg.n_flows,
                        fairness_coef=cfg.fairness_coef,
                        deadline_coef=cfg.deadline_coef,
                        max_active=cfg.max_active)
                )(tables, flows, objectives, roll_keys)
                # (E, M, F, ...) / rew (E, M)
            else:
                obs, act, rew, logp = jax.vmap(
                    lambda tab, k: _rollout(
                        params["policy"], env_params, tab, k,
                        M=cfg.max_steps, substeps=cfg.substeps, spec=spec,
                        backend=cfg.backend, randomize_t0=randomize_t0,
                        policy=cfg.policy)
                )(tables, roll_keys)  # (E, M, ...)
        if cfg.gae_lambda == 1.0:  # static: the paper's Monte-Carlo path
            ret = jax.vmap(_returns, in_axes=(0, None))(rew, cfg.gamma)
            if multi:
                # every (env, step, flow) sample trains against the SHARED
                # fleet return of its step; recurrent replay treats each
                # (env, flow) pair as one carry sequence
                ret = jnp.broadcast_to(ret[:, :, None], logp.shape)
                # (E, M, F)
        else:
            # lambda-returns bootstrap on the PRE-update critic: a fixed
            # baseline (data, not a differentiated graph) shared by all
            # ppo_epochs, matching how logp_old freezes the behavior policy
            if recurrent:
                def vseq(obs_seq):  # one episode from the zero carry
                    def stepfn(hv, o):
                        hv, v = nets.rnn_value_apply(params["value"], hv, o)
                        return hv, v
                    _, v = jax.lax.scan(stepfn,
                                        nets.rnn_carry(params["value"]),
                                        obs_seq)
                    return v
                if multi:  # (E,M,F,D) -> per-(env,flow) sequences
                    v = jax.vmap(jax.vmap(vseq))(obs.transpose(0, 2, 1, 3))
                    v = v.transpose(0, 2, 1)  # (E, M, F)
                else:
                    v = jax.vmap(vseq)(obs)  # (E, M)
            else:
                v = nets.value_apply(params["value"], obs)
            if multi:  # shared reward, per-flow baselines
                ret = jax.vmap(lambda r_e, v_e: jax.vmap(
                    lambda v_f: _gae_returns(r_e, v_f, cfg.gamma,
                                             cfg.gae_lambda),
                    in_axes=1, out_axes=1)(v_e))(rew, v)  # (E, M, F)
            else:
                ret = jax.vmap(
                    lambda r_e, v_e: _gae_returns(r_e, v_e, cfg.gamma,
                                                  cfg.gae_lambda))(rew, v)
        if multi:
            if recurrent:
                batch = (obs.transpose(0, 2, 1, 3)
                            .reshape(-1, cfg.max_steps, spec.dim),
                         act.transpose(0, 2, 1, 3)
                            .reshape(-1, cfg.max_steps, ACT_DIM),
                         ret.transpose(0, 2, 1).reshape(-1, cfg.max_steps),
                         logp.transpose(0, 2, 1).reshape(-1, cfg.max_steps))
            else:
                batch = (obs.reshape(-1, spec.dim),
                         act.reshape(-1, ACT_DIM),
                         ret.reshape(-1), logp.reshape(-1))
        elif recurrent:  # the loss replays carries over episode sequences
            batch = (obs, act, ret, logp)
        else:
            batch = (obs.reshape(-1, spec.dim), act.reshape(-1, ACT_DIM),
                     ret.reshape(-1), logp.reshape(-1))

        def update(carry, _):
            params, opt = carry
            (l, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, cfg)
            params, opt, _ = adamw_update(params, grads, opt, lr=cfg.lr,
                                          weight_decay=0.0,
                                          max_grad_norm=cfg.max_grad_norm)
            return (params, opt), l

        with jax.named_scope("update"):
            (params, opt), losses = jax.lax.scan(update, (params, opt), None,
                                                 length=cfg.ppo_epochs)
        ep_rewards = rew.sum(axis=1)  # (E,)
        return ({"params": params, "opt": opt}, ep_rewards, losses[-1])

    return jax.jit(episode)


def _broadcast_table(table, n_envs):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_envs,) + x.shape), table)


_LEGACY_KWARG_PAIRS = ("tables", "flows/resample_flows",
                       "objectives/resample_objectives",
                       "topology/resample_topology",
                       "faults/resample_faults")


def train_ppo(env_params, cfg: PPOConfig = None, *, workload=None,
              resample=None, tables=None, flows=None, resample_flows=None,
              objectives=None, resample_objectives=None, topology=None,
              resample_topology=None, faults=None, resample_faults=None,
              r_max=None, mesh=None, key=None):
    """Algorithm 2, schedule-native. Returns TrainResult with the BEST (not
    last) params.

    Rounds run one ahead: round r+1 is dispatched (its resample, its key
    and its episode program, queued on round r's output state) before
    round r's rewards are read back and selected on, so the host's work
    overlaps the device's. ``ceil(max_episodes / n_envs)`` rounds run, and
    the result is the one a round-at-a-time loop gives, bit for bit. The
    one difference a caller sees: ``resample(r + 1)`` is called before
    round r's convergence test, so a run that stops on ``r_max`` and
    ``patience`` calls ``resample`` once more, for a round it drops
    unread.

    ``workload``: a ``repro.core.Workload`` bundling everything one round
    runs on — batched ScheduleTable (leading axis cfg.n_envs; None = the
    params' static conditions), batched FlowSchedule activity windows
    (None = every flow active all episode), batched FlowObjective (None =
    the default objective — the objective-free reward, bit-for-bit),
    batched Topology (None = the single-bottleneck fleet world; when
    present the rollout swaps to the per-link work-conserving
    topology_step, the workload's tables are ignored, and episode start
    times randomize over the graph horizon), and per-env FaultSpec
    schedules (None = the fault-free world, bit-identical; when present
    each round's kills/hangs/blackouts are compiled into activity-window
    and capacity edits — ``Workload.compiled()`` — before the jitted
    episode, so the policy trains through liveness discontinuities).
    ``repro.scenarios.sample_fleet_batch`` / ``sample_topology_batch``
    return exactly this bundle.
    ``resample``: optional ``fn(round_index) -> Workload`` called before
    every episode batch to redraw the whole distribution (same shapes =>
    no retrace); an explicitly passed ``workload`` is honored for round 0,
    resampling starts at round 1. Whether the rollout is topology-mode is
    fixed by round 0 (the initial workload or ``resample(0)``).
    ``mesh``: optional 1-D jax Mesh over the flow axis
    (repro.launch.make_fleet_mesh) — every resampled FlowSchedule /
    FlowObjective / PathSpec batch is device_put with its F axis sharded
    (repro.sharding.fleet) before the jitted episode, so GSPMD partitions
    the rollout across devices. Combine with ``cfg.pad_flows`` so F always
    divides the mesh. ``cfg.max_active`` flows through to the sparse
    contention solve (fleet_step/topology_step ``max_active=``).

    DEPRECATED (one cycle, removal pinned in tests/test_faults.py): the
    per-axis kwarg pairs — ``tables``/``resample``-returning-tables,
    ``flows``/``resample_flows``, ``objectives``/``resample_objectives``,
    ``topology``/``resample_topology``, ``faults``/``resample_faults`` —
    emit DeprecationWarning and are folded into a Workload internally,
    compiling to the exact trace the bundled spelling compiles (pinned
    bitwise in tests/test_faults.py)."""
    cfg = cfg or PPOConfig()
    legacy = {"tables": tables, "flows": flows, "objectives": objectives,
              "topology": topology, "faults": faults,
              "resample_flows": resample_flows,
              "resample_objectives": resample_objectives,
              "resample_topology": resample_topology,
              "resample_faults": resample_faults}
    if any(v is not None for v in legacy.values()):
        warnings.warn(
            "train_ppo's per-axis kwarg pairs "
            f"({', '.join(_LEGACY_KWARG_PAIRS)}) are deprecated: bundle "
            "the axes in a repro.core.Workload and pass "
            "train_ppo(workload=..., resample=fn(round) -> Workload). "
            "The bundled path compiles to the identical trace.",
            DeprecationWarning, stacklevel=2)
        if workload is not None:
            raise ValueError("pass workload= or the legacy per-axis "
                             "kwargs, not both")
        workload = Workload(tables=tables, flows=flows,
                            objectives=objectives, topology=topology,
                            faults=faults)
    wl = workload if workload is not None else Workload()
    if cfg.pad_flows and cfg.n_flows > 1:
        cfg = dc_replace(cfg, n_flows=flow_bucket(cfg.n_flows))
    pad_to = cfg.n_flows if (cfg.pad_flows and cfg.n_flows > 1) else None
    key = key if key is not None else jax.random.PRNGKey(cfg.seed)
    k_init, key = jax.random.split(key)
    train_state = init_agent(k_init, cfg)
    if mesh is not None:
        # replicated on the mesh from round 0: the episode returns it so,
        # and a carry whose placement changed after round 0 would recompile
        from jax.sharding import NamedSharding, PartitionSpec
        train_state = jax.device_put(
            train_state, NamedSharding(mesh, PartitionSpec()))
    topo_mode = wl.topology is not None or resample_topology is not None
    scheduled = wl.tables is not None or resample is not None or topo_mode
    # defaults are filled per round AFTER resampling, from these constants
    # — the same broadcast arrays every round, so the trace never changes
    fill_tables = fill_flows = None
    if wl.tables is None and resample is None and not topo_mode:
        fill_tables = _broadcast_table(
            constant_table(env_params.tpt, env_params.bw, env_params.duration),
            cfg.n_envs)
    if ((cfg.n_flows > 1 or topo_mode) and wl.flows is None
            and resample_flows is None):
        fill_flows = _broadcast_table(always_on(cfg.n_flows), cfg.n_envs)
    # objectives=None stays None (an empty pytree vmaps fine): the
    # objective-blind fleet keeps the exact PR 4 trace instead of a
    # broadcast default — fleet_step folds the defaults in-graph
    episode_fn = _make_episode_fn(env_params, cfg, randomize_t0=scheduled,
                                  topology=topo_mode)

    best_r = -jnp.inf
    best_sel = -jnp.inf  # selection metric (batch_mean mode)
    best_params = train_state["params"]
    stagnant = 0
    converged_at = None
    history = []
    t0 = time.time()
    n_episodes = 0
    n_rounds = max(0, -(-cfg.max_episodes // cfg.n_envs))
    by_batch_mean = cfg.param_selection == "batch_mean"
    warned_table_resample = False

    def world(rnd):
        """Round ``rnd``'s inputs: the resample, ``Workload.compiled()``,
        the defaults, flow padding and mesh placement."""
        nonlocal wl, warned_table_resample
        if resample is not None and ((wl.tables is None
                                      and wl.topology is None) or rnd > 0):
            out = resample(rnd)
            if isinstance(out, Workload):
                wl = out
            else:  # legacy fn(round) -> batched tables
                if not warned_table_resample:
                    warned_table_resample = True
                    warnings.warn(
                        "train_ppo(resample=...) returning bare tables "
                        "is deprecated: return a repro.core.Workload",
                        DeprecationWarning, stacklevel=4)
                wl = wl.replace(tables=out)
        if resample_flows is not None and (wl.flows is None or rnd > 0):
            wl = wl.replace(flows=resample_flows(rnd))
        if resample_objectives is not None and (wl.objectives is None
                                                or rnd > 0):
            wl = wl.replace(objectives=resample_objectives(rnd))
        if resample_topology is not None and (wl.topology is None
                                              or rnd > 0):
            wl = wl.replace(topology=resample_topology(rnd))
        if resample_faults is not None and (wl.faults is None or rnd > 0):
            wl = wl.replace(faults=resample_faults(rnd))
        run = wl.compiled()  # fault edits (no faults -> wl itself)
        tables_r = run.tables if run.tables is not None else fill_tables
        flows_r = run.flows if run.flows is not None else fill_flows
        objectives_r, topology_r = run.objectives, run.topology
        if pad_to is not None and flows_r is not None:
            flows_r = pad_flow_schedule(flows_r, pad_to)
            objectives_r = pad_flow_objectives(objectives_r, pad_to)
            if topology_r is not None:
                topology_r = Topology(
                    graph=topology_r.graph,
                    paths=pad_path_spec(topology_r.paths, pad_to))
        if mesh is not None:
            from repro.sharding.fleet import (shard_flow_schedule,
                                              shard_flow_objectives,
                                              shard_path_spec)
            if flows_r is not None:
                flows_r = shard_flow_schedule(flows_r, mesh)
            objectives_r = shard_flow_objectives(objectives_r, mesh)
            if topology_r is not None:
                topology_r = Topology(
                    graph=topology_r.graph,
                    paths=shard_path_spec(topology_r.paths, mesh))
        return tables_r, flows_r, objectives_r, topology_r

    def dispatch(rnd, state, prev_rewards=None):
        """Round ``rnd``'s episode program, queued behind the round whose
        outputs (``state``, ``prev_rewards``) it starts from."""
        nonlocal key
        inputs = world(rnd)
        if prev_rewards is not None and not prev_rewards.is_ready():
            mark("ppo.dispatch_ahead")
        with span("ppo.dispatch"):
            key, k = jax.random.split(key)
            return episode_fn(state, *inputs, k)

    # one round in flight ahead of the one being read: round r+1 is
    # dispatched before round r's rewards are copied back, so the host's
    # dispatch, read-back and selection run while the device works
    ahead = None
    for rnd in range(n_rounds):
        # one step-view span per round: round r's read-back and selection,
        # after round r+1's world and dispatch (round 0's own too, in the
        # first); its self time is the resample and Workload.compiled()
        with span("ppo.round", step_num=rnd):
            state_r, ep_rewards, loss = (ahead if ahead is not None
                                         else dispatch(0, train_state))
            ahead = (dispatch(rnd + 1, state_r, ep_rewards)
                     if rnd + 1 < n_rounds else None)
            with span("ppo.rewards"):
                ep_rewards = jax.device_get(ep_rewards)
            with span("ppo.select"):
                if by_batch_mean:
                    batch_mean = float(ep_rewards.mean())
                    if batch_mean > best_sel:
                        best_sel = batch_mean
                        with span("ppo.best_copy"):
                            best_params = jax.device_get(state_r["params"])
                        stagnant = 0
                    else:
                        stagnant += len(ep_rewards)
                for r in ep_rewards:
                    n_episodes += 1
                    history.append(float(r))
                    if r > best_r:
                        best_r = float(r)
                        if not by_batch_mean:
                            with span("ppo.best_copy"):
                                best_params = jax.device_get(
                                    state_r["params"])
                            stagnant = 0
                    elif not by_batch_mean:
                        stagnant += 1
                if cfg.log_every and n_episodes % cfg.log_every < cfg.n_envs:
                    print(f"[ppo] ep={n_episodes} best={best_r:.3f} "
                          f"loss={float(loss):.3f}", flush=True)
                if r_max is not None:
                    if (converged_at is None
                            and best_r >= (cfg.convergence_frac * r_max
                                           * cfg.max_steps)):
                        converged_at = n_episodes
                    if converged_at is not None and stagnant >= cfg.patience:
                        break  # the round in flight is dropped unread

    return TrainResult(params=best_params, episodes=n_episodes,
                       wall_s=time.time() - t0, history=history,
                       converged_at=converged_at, best_reward=float(best_r),
                       r_max=r_max)

# train_ppo_vectorized was removed after its one-cycle deprecation horizon:
# train_ppo(env_params, PPOConfig(n_envs=...)) is the same fast path
# (removal pinned in tests/test_fleet.py).
