"""Training cells: ``repro.core.ppo.train_ppo`` under the configuration's
static table, its rounds timed from the outside and its first three
rounds checked against the plain reference.

One ``train_ppo`` call is the whole run. Round 0 compiles; rounds 0-2 are
set-up and are the ones the reference follows; the window opens as round
3 starts and closes at the first round start past ``--seconds``, so it
holds whole rounds of the trainer's own loop, the host work between
episode programs included. At both ends the harness first waits until the
device has finished every round dispatched before, so a round counts only
once its work is done. The benchmark sees each round through the
trainer's episode program, which it wraps as the trainer builds it
(``repro.core.ppo._make_episode_fn``): the wrapper stamps the time as each
round starts and keeps the train state the first rounds hand back.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from . import check, flops
from .device import warn

CHECK_ROUNDS = 3
# the configuration's ``matmul_precision`` -> the reference's arithmetic
PRECISION = {"default": "float32_default", "highest": "float32"}


class WindowClosed(Exception):
    pass


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


class Rounds:
    """Stamps each round's start, opens and closes the window, keeps what
    the first rounds produced."""

    def __init__(self, seconds, spans, tracer=None, compiles=None):
        self.seconds = seconds
        self.spans = spans
        self.tracer = tracer
        self.compiles = compiles
        self.stamps = []
        self.kept = []
        self.p0 = None
        self.last = None
        self.out = None
        self.window = None
        self.compiles_in_window = 0
        self._ann = None

    def wrap(self, fn):
        @functools.wraps(fn)
        def episode(*args):
            r = self.enter(args)
            with self.spans.span("episode"):
                out = fn(*args)
            if r < CHECK_ROUNDS:
                self.kept.append(out)
            self.last = (fn, args)
            self.out = out
            return out
        return episode

    def _settled(self):
        """The time once the device has finished every round dispatched
        so far (each round takes the one before's state, so waiting on the
        last output waits on all)."""
        import jax
        jax.block_until_ready(self.out)
        return time.perf_counter()

    def enter(self, args):
        r = len(self.stamps)
        if r == 0:
            self.p0 = args[0]["params"]
        if r == CHECK_ROUNDS:
            self._settled()
            if self.tracer is not None:
                self.tracer.start()
                self._ann = self.tracer.annotation()
            t = time.perf_counter()
            self.window = [t, None]
            self._c0 = self.compiles.n if self.compiles else 0
        elif r > CHECK_ROUNDS and time.perf_counter() - self.window[0] \
                >= self.seconds:
            t = self._settled()
            self.window[1] = t
            self.stamps.append(t)
            if self.compiles:
                self.compiles_in_window = self.compiles.n - self._c0
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self.tracer.stop()
            raise WindowClosed
        else:
            t = time.perf_counter()
        self.stamps.append(t)
        return r

    @property
    def window_rounds(self):
        return len(self.stamps) - 1 - CHECK_ROUNDS


def run_program(config, traffic, seed, seconds, spans, tracer=None,
                compiles=None):
    """Drive ``train_ppo`` through set-up and the window. Returns the
    ``Rounds`` record."""
    import jax
    from repro.core import ppo

    p = env_params(config)
    cfg = ppo_config(config, traffic, seed)
    rounds = Rounds(seconds, spans, tracer, compiles)
    orig = ppo._make_episode_fn

    def make_episode_fn(*a, **k):
        return rounds.wrap(orig(*a, **k))

    ppo._make_episode_fn = make_episode_fn
    try:
        with spans.span("train_ppo"):
            ppo.train_ppo(p, cfg)
    except WindowClosed:
        pass
    finally:
        ppo._make_episode_fn = orig
    jax.effects_barrier()
    return rounds


def program_numbers(rounds):
    import jax
    kept = jax.device_get(rounds.kept)
    return {"loss": [float(k[2]) for k in kept],
            "reward_mean": [float(np.mean(k[1])) for k in kept],
            "rewards": [np.asarray(k[1], np.float64) for k in kept],
            "m0": kept[0][0]["opt"]["m"],
            "v0": kept[0][0]["opt"]["v"],
            "p0": jax.device_get(rounds.p0),
            "p3": kept[CHECK_ROUNDS - 1][0]["params"]}


def temp_bytes(rounds):
    """Compiled temporaries of the episode program, from its own
    compile (served from the cache)."""
    fn, args = rounds.last
    try:
        return int(fn.lower(*args).compile().memory_analysis()
                   .temp_size_in_bytes)
    except Exception as e:  # a reading, not a result: say so and go on
        warn(f"no memory_analysis of the episode program: {e!r}")
        return 0


def reference(config, traffic, seed, dtype=None, **fault):
    """The plain reference's first three rounds from the same seed: the
    same numbers ``program_numbers`` gives. ``dtype`` defaults to the
    precision the configuration states; ``fault`` plants one of
    ``sim.ppo_round``'s faults."""
    import jax
    import jax.numpy as jnp
    from ref import nets, sim
    dtype = dtype or PRECISION[config["matmul_precision"]]
    dt = {"float32": jnp.float32, "float32_default": nets.F32_DEFAULT,
          "float8": nets.F8}[dtype]
    a = dict(config["agent"])
    s = seed % (1 << 31)
    params, opt = nets.init_agent(s, a, obs_dim(config))
    p0 = params
    step = jax.jit(functools.partial(
        sim.ppo_round, env=sim.env_of(config), agent=a,
        n_envs=traffic["n_envs"], dtype=dt, **fault))
    out = {"loss": [], "reward_mean": [], "rewards": []}
    for r, key in enumerate(nets.round_keys(s, CHECK_ROUNDS)):
        params, opt, loss, rew = step(params, opt, key)
        if r == 0:
            out["m0"] = jax.device_get(opt["m"])
            out["v0"] = jax.device_get(opt["v"])
        out["loss"].append(float(loss))
        out["reward_mean"].append(float(jnp.mean(rew)))
        out["rewards"].append(np.asarray(rew, np.float64))
    out["p0"] = jax.device_get(p0)
    out["p3"] = jax.device_get(params)
    return out


def round_flops(config, traffic):
    a = config["agent"]
    samples = traffic["n_envs"] * a["max_steps"]
    return flops.round_flops(obs_dim(config), a["hidden"], samples,
                             a["ppo_epochs"])


def run(config, traffic, seed, seconds, spans, tracer, compiles, t_start):
    """The whole training run; returns (end_to_end, context for the
    per-layer readers, the program's numbers, extra memory bytes,
    counts)."""
    rounds = run_program(config, traffic, seed, seconds, spans, tracer,
                         compiles)
    ws, we = rounds.window
    n = rounds.window_rounds
    steps = n * traffic["n_envs"] * config["agent"]["max_steps"]
    e2e = {"train_env_steps_per_s": steps / (we - ws),
           "setup_s": ws - t_start}
    ctx = {"rounds": n, "window": (ws, we),
           "flops_per_round": round_flops(config, traffic),
           "program": "jit_episode"}
    prog = program_numbers(rounds)
    extra = temp_bytes(rounds)
    counts = {"attempted": n, "failed": 0,
              "compiles_in_window": rounds.compiles_in_window}
    del rounds
    gc.collect()
    return e2e, ctx, prog, extra, counts


def numbers(config, traffic, seed, prog):
    return check.train_numbers(prog, reference(config, traffic, seed))
