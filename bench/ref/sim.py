"""Plain simulator of one transfer under a static table, and the PPO round
the trainer runs on it.

From the paper (§IV-B, Algorithm 1) and the configuration: a simulated
second is ``substeps`` sub-intervals; in each, every stage moves at
``min(threads * TPT, B)`` and bytes pass read -> sender buffer -> network
-> receiver buffer -> write. The reward is sum_stage tps/k^threads.
Rates are float32; the matrix products run at the precision ``nets`` is
given.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import nets

F32 = jnp.float32


def env_of(config):
    """The simulator's constants from a configuration file."""
    e = config["env"]
    return {"substeps": e["substeps"], "duration": float(e["duration_s"]),
            "tpt": jnp.asarray(e["tpt"], F32), "bw": jnp.asarray(e["bw"], F32),
            "cap": jnp.asarray(e["cap"], F32), "n_max": float(e["n_max"]),
            "k": float(e["k"])}


def _rates(threads, env):
    """(S, 1, 3) per-stage rates over one interval: the same in every
    sub-interval, since the table has one time bin."""
    r = jnp.minimum(threads * env["tpt"], env["bw"])
    return jnp.broadcast_to(r, (env["substeps"],) + r.shape)


def _move(bufs, rates, env):
    """Push (S, 1, 3) rates through the two staging buffers."""
    dt = env["duration"] / env["substeps"]
    cap = env["cap"]

    def sub(b, r):
        s, q = b[:, 0], b[:, 1]
        read = jnp.maximum(jnp.minimum(r[:, 0] * dt, cap[0] - s), 0.0)
        s = s + read
        net = jnp.maximum(jnp.minimum(jnp.minimum(r[:, 1] * dt, s),
                                      cap[1] - q), 0.0)
        q = q + net
        wr = jnp.maximum(jnp.minimum(r[:, 2] * dt, q), 0.0)
        return jnp.stack([s - net, q - wr], -1), jnp.stack([read, net, wr],
                                                            -1)

    bufs, moved = jax.lax.scan(sub, bufs, rates)
    return bufs, moved.sum(0) / env["duration"]


def _observe(bufs, threads, tps, env):
    """(1, 8) observation: threads, throughputs and free buffer space,
    each over its own scale."""
    bw_ref = jnp.maximum(jnp.max(env["bw"]), 1e-9)
    cap = env["cap"]
    return jnp.concatenate([threads / env["n_max"], tps / bw_ref,
                            (cap - bufs) / cap], -1)


def rollout(pol, key, env, agent, dtype=F32):
    """One episode: reset to random threads, one warm-up interval, then M
    policy steps. Returns obs (M,1,D), act (M,1,3), rew (M,), logp (M,1)."""
    M = agent["max_steps"]
    k_reset, k_steps = jax.random.split(key)
    threads = jax.random.randint(k_reset, (3,), 1, 16).astype(F32)
    threads = threads.reshape(1, 3)
    bufs, tps = _move(jnp.zeros((1, 2), F32), _rates(threads, env), env)
    obs0 = _observe(bufs, threads, tps, env)

    def step(carry, k):
        bufs, obs = carry
        mean, std = nets.policy_mean_std(pol, obs, dtype)
        a = mean + std * jax.random.normal(k, (3,)).reshape(1, 3)
        logp = nets.gaussian_logp(mean, std, a)
        n = jnp.clip(jnp.round(a), 1.0, env["n_max"])
        bufs2, tps2 = _move(bufs, _rates(n, env), env)
        rew = jnp.sum(tps2 / jnp.power(env["k"], n))
        return (bufs2, _observe(bufs2, n, tps2, env)), (obs, a, rew, logp)

    _, traj = jax.lax.scan(step, (bufs, obs0), jax.random.split(k_steps, M))
    return traj


def ppo_round(params, opt, key, env, agent, n_envs, dtype=F32, keep=None,
              frozen=False, reward_scale=1.0):
    """One training round: ``n_envs`` episodes, then ``ppo_epochs`` AdamW
    steps on every (env, step) sample against its step's return. Returns
    (params, opt, last epoch's loss, episode rewards).

    The faults a sound comparison has to catch, planted here when the
    reference is put in the program's place: ``keep`` (a row mask) leaves
    rows out of the batch, the means taken over the rest; ``frozen`` hands
    the state back unchanged; ``reward_scale`` alters every reward where
    the environment produces it."""
    k_roll, _ = jax.random.split(key)
    obs, act, rew, logp = jax.vmap(
        lambda k: rollout(params["policy"], k, env, agent, dtype)
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
