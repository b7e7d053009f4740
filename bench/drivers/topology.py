"""Driver of the multi-link deployment: F always-on transfers routed over
the configuration's ``graph`` of E links, one policy shared by every
flow (``train_ppo`` with a ``Workload`` whose ``topology`` is the graph
and its paths, ``n_flows`` F and the topology observation), and the plain
reference ``bench/ref/topology.py`` with ``bench/ref/nets.py``.

A driver is what one deployment kind is to the training harness
(``harness.train``), which finds it by the configuration's ``"driver"``
key (``harness.spec.load_driver``) and calls the functions of
``harness.spec.DRIVER_FUNCTIONS``.
"""

from __future__ import annotations

import functools

import numpy as np

from harness import flops

OBS = {"topology": 19}


def env_params(config):
    from repro.core.simulator import make_env_params
    e = config["env"]
    return make_env_params(tpt=e["tpt"], bw=e["bw"], cap=e["cap"],
                           n_max=e["n_max"], duration=e["duration_s"],
                           k=e["k"])


def n_flows(config):
    return len(config["graph"]["flows"])


def obs_dim(config):
    return OBS[config["agent"]["obs"]]


def topology(config, n_envs):
    """The graph and its static routes, the same in each of ``n_envs``
    envs. Every link holds the configuration's ``tpt`` and ``bw`` in one
    time bin of one interval's length, so an episode's start, drawn over
    the bin less the episode, is 0."""
    import jax
    import jax.numpy as jnp
    from repro.core.topology import (Topology, make_link_graph,
                                     make_path_spec)
    e, g = config["env"], config["graph"]
    links = g["links"]
    onpath = np.zeros((len(g["flows"]), len(links)), np.float32)
    for i, f in enumerate(g["flows"]):
        onpath[i, [links.index(name) for name in f["links"]]] = 1.0
    shape = (len(links), 1, 3)
    one = Topology(
        graph=make_link_graph(np.broadcast_to(e["tpt"], shape),
                              np.broadcast_to(e["bw"], shape),
                              bin_seconds=e["duration_s"]),
        paths=make_path_spec(onpath))
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_envs,) + x.shape),
                        one)


def ppo_config(config, traffic, seed):
    """Every trainer setting the configuration and traffic state, spelled
    out, so a changed default in the program cannot change the cell."""
    from repro.core.ppo import PPOConfig
    from repro.core.simulator import TOPOLOGY_OBS
    a, e = config["agent"], config["env"]
    if a["objectives"] is not None:
        raise ValueError("the topology driver runs the default objectives")
    return PPOConfig(
        max_steps=a["max_steps"], max_episodes=1 << 62, lr=a["lr"],
        gamma=a["gamma"], gae_lambda=1.0, clip_eps=a["clip_eps"],
        entropy_coef=a["entropy_coef"], critic_coef=a["critic_coef"],
        ppo_epochs=a["ppo_epochs"], normalize_adv=True,
        n_envs=traffic["n_envs"], substeps=e["substeps"],
        patience=1 << 62, convergence_frac=0.9,
        action_scale=a["action_scale"], init_log_std=a["init_log_std"],
        max_grad_norm=a["max_grad_norm"], seed=seed % (1 << 31), log_every=0,
        obs_spec={"topology": TOPOLOGY_OBS}[a["obs"]],
        history=4, rnn_hidden=64,
        policy=a["policy"], backend=config["backend"],
        n_flows=n_flows(config), fairness_coef=a["fairness_coef"],
        deadline_coef=1.0, max_active=a["max_active"],
        pad_flows=config["pad_flows"], param_selection="best_episode")


def trainer(config, traffic, seed):
    """``(args, kwargs)`` of ``repro.core.ppo.train_ppo``."""
    from repro.core.workload import Workload
    if traffic["arrivals"] != "always_on":
        raise ValueError(f"the topology driver runs always-on flows, not "
                         f"{traffic['arrivals']!r}")
    cfg = ppo_config(config, traffic, seed)
    return (env_params(config),), {
        "cfg": cfg,
        "workload": Workload(topology=topology(config, traffic["n_envs"]))}


def reference(config, traffic, seed, dtype, **fault):
    """The plain reference from the same seed: its initial params and
    AdamW state, and ``step(params, opt, r)``, which runs round ``r`` on
    that round's key and returns (params, opt, loss, episode rewards).
    ``dtype`` names the reference's arithmetic (``"float32"``,
    ``"float32_default"`` or ``"float8"``); ``fault`` plants one of
    ``faults``."""
    import jax
    import jax.numpy as jnp
    from ref import nets
    from ref import topology as ref_topology
    dt = {"float32": jnp.float32, "float32_default": nets.F32_DEFAULT,
          "float8": nets.F8}[dtype]
    a = dict(config["agent"])
    s = seed % (1 << 31)
    params, opt = nets.init_agent(s, a, obs_dim(config))
    ppo_round = jax.jit(functools.partial(
        ref_topology.ppo_round, world=ref_topology.world_of(config), agent=a,
        n_envs=traffic["n_envs"], dtype=dt, **fault))

    def step(params, opt, r):
        return ppo_round(params, opt, nets.round_keys(s, r + 1)[r])
    return params, opt, step


def faults(config, traffic):
    """The faults the cell can have, each as the keywords of ``reference``
    that plant it in ``ref.topology.ppo_round``: half of the batch left
    out, the means taken over the rest; the state handed back unchanged;
    every reward 1% larger where the environment produces it; every flow
    on a link given the link's whole capacity instead of its thread
    share."""
    import jax.numpy as jnp
    n = steps_per_round(config, traffic) * n_flows(config)
    return {"half_batch": {"keep": jnp.arange(n) < n // 2},
            "state_unchanged": {"frozen": True},
            "reward_altered": {"reward_scale": 1.01},
            "split_ignored": {"split": False}}


def steps_per_round(config, traffic):
    """World-steps: each env's step moves all F flows at once."""
    return traffic["n_envs"] * config["agent"]["max_steps"]


def round_flops(config, traffic):
    """One update row per (env, step, flow)."""
    a = config["agent"]
    return flops.round_flops(obs_dim(config), a["hidden"],
                             steps_per_round(config, traffic)
                             * n_flows(config), a["ppo_epochs"])
