"""Driver of the single-flow deployment: one transfer under the
configuration's static table (``env``), the actor-critic of its
``agent``, and the plain reference ``bench/ref/sim.py`` with
``bench/ref/nets.py``.

A driver is what one deployment kind is to the training harness
(``harness.train``), which finds it by the configuration's ``"driver"``
key (``harness.spec.load_driver``) and calls the functions of
``harness.spec.DRIVER_FUNCTIONS``.
"""

from __future__ import annotations

import functools

from harness import flops


def env_params(config):
    from repro.core.simulator import make_env_params
    e = config["env"]
    return make_env_params(tpt=e["tpt"], bw=e["bw"], cap=e["cap"],
                           n_max=e["n_max"], duration=e["duration_s"],
                           k=e["k"])


def obs_dim(config):
    return {"base": 8}[config["agent"]["obs"]]


def ppo_config(config, traffic, seed):
    """Every trainer setting the configuration and traffic state, spelled
    out, so a changed default in the program cannot change the cell."""
    from repro.core.ppo import PPOConfig
    from repro.core.simulator import DEFAULT_OBS
    a, e = config["agent"], config["env"]
    return PPOConfig(
        max_steps=a["max_steps"], max_episodes=1 << 62, lr=a["lr"],
        gamma=a["gamma"], gae_lambda=1.0, clip_eps=a["clip_eps"],
        entropy_coef=a["entropy_coef"], critic_coef=a["critic_coef"],
        ppo_epochs=a["ppo_epochs"], normalize_adv=True,
        n_envs=traffic["n_envs"], substeps=e["substeps"],
        patience=1 << 62, convergence_frac=0.9,
        action_scale=a["action_scale"], init_log_std=a["init_log_std"],
        max_grad_norm=a["max_grad_norm"], seed=seed % (1 << 31), log_every=0,
        obs_spec={"base": DEFAULT_OBS}[a["obs"]],
        policy=a["policy"], backend=config["backend"], n_flows=1,
        fairness_coef=0.0, deadline_coef=1.0, max_active=None,
        pad_flows=config["pad_flows"], param_selection="best_episode")


def trainer(config, traffic, seed):
    """``(args, kwargs)`` of ``repro.core.ppo.train_ppo``."""
    return (env_params(config),), {"cfg": ppo_config(config, traffic, seed)}


def reference(config, traffic, seed, dtype, **fault):
    """The plain reference from the same seed: its initial params and
    AdamW state, and ``step(params, opt, r)``, which runs round ``r`` on
    that round's key and returns (params, opt, loss, episode rewards).
    ``dtype`` names the reference's arithmetic (``"float32"``,
    ``"float32_default"`` or ``"float8"``); ``fault`` plants one of
    ``faults``."""
    import jax
    import jax.numpy as jnp
    from ref import nets, sim
    dt = {"float32": jnp.float32, "float32_default": nets.F32_DEFAULT,
          "float8": nets.F8}[dtype]
    a = dict(config["agent"])
    s = seed % (1 << 31)
    params, opt = nets.init_agent(s, a, obs_dim(config))
    ppo_round = jax.jit(functools.partial(
        sim.ppo_round, env=sim.env_of(config), agent=a,
        n_envs=traffic["n_envs"], dtype=dt, **fault))

    def step(params, opt, r):
        return ppo_round(params, opt, nets.round_keys(s, r + 1)[r])
    return params, opt, step


def faults(config, traffic):
    """The faults the cell can have, each as the keywords of ``reference``
    that plant it in ``sim.ppo_round``: half of the batch left out, the
    means taken over the rest; the state handed back unchanged; every
    reward 1% larger where the environment produces it."""
    import jax.numpy as jnp
    n = steps_per_round(config, traffic)
    return {"half_batch": {"keep": jnp.arange(n) < n // 2},
            "state_unchanged": {"frozen": True},
            "reward_altered": {"reward_scale": 1.01}}


def steps_per_round(config, traffic):
    return traffic["n_envs"] * config["agent"]["max_steps"]


def round_flops(config, traffic):
    a = config["agent"]
    return flops.round_flops(obs_dim(config), a["hidden"],
                             steps_per_round(config, traffic),
                             a["ppo_epochs"])
