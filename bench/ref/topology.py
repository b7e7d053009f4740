"""Plain simulator of F always-on transfers routed over a graph of E links,
and the PPO round the trainer runs on it with one policy shared by every
flow.

From the configuration's ``graph``: each flow crosses the links its
``links`` names. In every sub-interval each link shares its capacity among
the flows that cross it in proportion to their threads, stage by stage,
and a flow moves at ``min(threads * TPT, its share of B)`` on each of its
links; its rate is the least of those over its links. Bytes then pass
read -> sender buffer -> network -> receiver buffer -> write as in
``sim.py``. The shared reward is the sum over flows of sum_stage
tps/k^threads. Each flow observes its own 8 paper features, 5 of change
(throughput deltas, buffer drains), 3 of the fleet (active share, total
network throughput, its share of it) and 3 of the graph (the load of the
most loaded link on its path, its path's length over E, its share of that
link's load). Rates are float32; the matrix products run at the precision
``nets`` is given.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import nets, sim

F32 = jnp.float32


def world_of(config):
    """The simulator's constants and the routing matrix (F, E) from a
    configuration file."""
    e, g = config["env"], config["graph"]
    links = g["links"]
    onpath = [[1.0 if name in f["links"] else 0.0 for name in links]
              for f in g["flows"]]
    return {"substeps": e["substeps"], "duration": float(e["duration_s"]),
            "tpt": jnp.asarray(e["tpt"], F32), "bw": jnp.asarray(e["bw"], F32),
            "cap": jnp.asarray(e["cap"], F32), "n_max": float(e["n_max"]),
            "k": float(e["k"]), "onpath": jnp.asarray(onpath, F32)}


def link_rates(threads, world, split=True):
    """(F, E, 3): what each flow could move on each link of its path
    (threads x TPT, held to its thread share of the link), +inf off its
    path. ``split=False`` plants the fault of a link that gives every
    flow its whole capacity."""
    on = world["onpath"][:, :, None]                    # (F, E, 1)
    n = threads[:, None, :] * on                        # (F, E, 3)
    total = n.sum(axis=0)                               # (E, 3)
    share = (world["bw"] * n / jnp.maximum(total, 1e-9) if split
             else jnp.broadcast_to(world["bw"], n.shape))
    r = jnp.minimum(n * world["tpt"], share)
    return jnp.where(on > 0, r, jnp.inf)


def rates(threads, world, split=True):
    """(S, F, 3) per-flow rates over one interval: the least over each
    flow's links, the same in every sub-interval (one time bin, static
    routes, every flow on)."""
    r = link_rates(threads, world, split).min(axis=1)
    return jnp.broadcast_to(r, (world["substeps"],) + r.shape)


def binds(threads, world):
    """(F, E) True where a link's thread share holds a flow's network
    stage below what its threads could move (share x B < threads x TPT),
    on the flow's path; False off it."""
    on = world["onpath"]
    n = threads[:, 1][:, None] * on
    share = world["bw"][1] * n / jnp.maximum(n.sum(axis=0), 1e-9)
    return (on > 0) & (share < n * world["tpt"][1])


def observe(bufs, threads, tps, prev, world):
    """(F, 19) observation of every flow."""
    bw_ref = jnp.max(world["bw"])
    cap, dur = world["cap"], world["duration"]
    on = world["onpath"]
    net = tps[:, 1]
    total = net.sum()
    load = (on * net[:, None]).sum(axis=0)              # (E,) per link
    util = load / bw_ref
    worst = jnp.argmax(jnp.where(on > 0, util, -jnp.inf), axis=1)
    f = net.shape[0]
    return jnp.concatenate([
        threads / world["n_max"], tps / bw_ref, (cap - bufs) / cap,
        (tps - prev) / bw_ref,
        ((tps[:, 1] - tps[:, 0]) * dur / cap[0])[:, None],
        ((tps[:, 2] - tps[:, 1]) * dur / cap[1])[:, None],
        jnp.ones((f, 1), F32),
        jnp.full((f, 1), total / bw_ref),
        (net / jnp.maximum(total, 1e-9))[:, None],
        util[worst][:, None],
        (on.sum(axis=1) / on.shape[1])[:, None],
        (net / jnp.maximum(load[worst], 1e-9))[:, None]], axis=-1)


def rollout(pol, key, world, agent, dtype=F32, split=True):
    """One episode of every flow: reset to random threads, one warm-up
    interval, then M steps of the shared policy. Returns obs (M,F,D),
    act (M,F,3), rew (M,), logp (M,F). The episode starts at time 0: its
    start is drawn over the one-second time bin less the episode's 11 s,
    which leaves none."""
    M = agent["max_steps"]
    f = world["onpath"].shape[0]
    k_reset, _, k_steps = jax.random.split(key, 3)
    threads = jax.random.randint(k_reset, (f, 3), 1, 16).astype(F32)
    bufs, tps = sim._move(jnp.zeros((f, 2), F32),
                          rates(threads, world, split), world)
    obs0 = observe(bufs, threads, tps, tps, world)

    def step(carry, k):
        bufs, tps, obs = carry
        mean, std = nets.policy_mean_std(pol, obs, dtype)
        a = mean + std * jax.random.normal(k, (f, 3))
        logp = nets.gaussian_logp(mean, std, a)
        n = jnp.clip(jnp.round(a), 1.0, world["n_max"])
        bufs2, tps2 = sim._move(bufs, rates(n, world, split), world)
        rew = jnp.sum(tps2 / jnp.power(world["k"], n))
        return ((bufs2, tps2, observe(bufs2, n, tps2, tps, world)),
                (obs, a, rew, logp))

    _, traj = jax.lax.scan(step, (bufs, tps, obs0),
                           jax.random.split(k_steps, M))
    return traj


def binding_share(params, key, world, agent, n_envs):
    """Share of the on-path (flow, link) pairs, over every interval of the
    round's episodes (warm-up included), whose network stage the link's
    thread share holds below what its threads could move. Threads are
    constant within an interval and the graph is static, so each interval
    stands for its sub-intervals."""
    k_roll, _ = jax.random.split(key)
    f = world["onpath"].shape[0]

    def episode(k):
        k_reset = jax.random.split(k, 3)[0]
        t0 = jax.random.randint(k_reset, (f, 3), 1, 16).astype(F32)
        _, act, _, _ = rollout(params["policy"], k, world, agent,
                               nets.F32_DEFAULT)
        n = jnp.clip(jnp.round(act), 1.0, world["n_max"])
        threads = jnp.concatenate([t0[None], n])        # (M+1, F, 3)
        return jax.vmap(lambda t: binds(t, world))(threads).sum()

    hits = jax.vmap(episode)(jax.random.split(k_roll, n_envs)).sum()
    pairs = world["onpath"].sum() * (agent["max_steps"] + 1) * n_envs
    return hits / pairs


def ppo_round(params, opt, key, world, agent, n_envs, dtype=F32, keep=None,
              frozen=False, reward_scale=1.0, split=True):
    """One training round: ``n_envs`` episodes of every flow, then
    ``ppo_epochs`` AdamW steps on every (env, step, flow) sample against
    its step's shared return. Returns (params, opt, last epoch's loss,
    episode rewards).

    The faults a sound comparison has to catch, planted here when the
    reference is put in the program's place: ``keep`` (a row mask) leaves
    rows out of the batch, the means taken over the rest; ``frozen`` hands
    the state back unchanged; ``reward_scale`` alters every reward where
    the environment produces it; ``split=False`` gives every flow on a
    link the link's whole capacity."""
    k_roll, _ = jax.random.split(key)
    obs, act, rew, logp = jax.vmap(
        lambda k: rollout(params["policy"], k, world, agent, dtype, split)
    )(jax.random.split(k_roll, n_envs))
    rew = rew * reward_scale
    ret = jax.vmap(lambda r: nets.discounted_returns(r, agent["gamma"]))(rew)
    ret = jnp.broadcast_to(ret[:, :, None], logp.shape)
    D = obs.shape[-1]
    batch = (obs.reshape(-1, D), act.reshape(-1, 3), ret.reshape(-1),
             logp.reshape(-1))
    loss = jnp.zeros((), F32)
    new_params, new_opt = params, opt
    for _ in range(agent["ppo_epochs"]):
        loss, grads = nets.ppo_epoch_grads(new_params, batch, agent, dtype,
                                           keep)
        new_params, new_opt = nets.adamw_step(new_params, grads, new_opt,
                                              agent)
    if frozen:
        return params, opt, loss, rew.sum(axis=1)
    return new_params, new_opt, loss, rew.sum(axis=1)
