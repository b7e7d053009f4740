"""Training cells: ``repro.core.ppo.train_ppo`` with the arguments the
configuration's driver (``bench/drivers/<name>.py``) gives, its rounds
timed from the outside and its first three rounds checked against the
driver's plain reference.

One ``train_ppo`` call is the whole run. Round 0 compiles; rounds 0-2 are
set-up and are the ones the reference follows; the window opens as round
3 starts and closes at the first round start past ``--seconds``, so it
holds whole rounds of the trainer's own loop, the host work between
episode programs included. At both ends the harness first waits until the
device has finished every round dispatched before, so a round counts only
once its work is done. The benchmark sees each round through the
trainer's episode program, which it wraps as the trainer builds it
(``repro.core.ppo._make_episode_fn``): the wrapper stamps the time as each
round starts. What set-up's rounds produced is kept as host copies, each
taken before the round that takes it as input is dispatched, so a program
that donates its train state deletes nothing the harness reads later.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from . import check, spec
from .device import warn

CHECK_ROUNDS = 3
# the configuration's ``matmul_precision`` -> the reference's arithmetic
PRECISION = {"default": "float32_default", "highest": "float32"}


class WindowClosed(Exception):
    pass


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
            self.enter(args)
            with self.spans.span("episode"):
                out = fn(*args)
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

    def _keep(self, r, args):
        """Host copies of what set-up's rounds produced, each taken before
        the round that takes it as input is dispatched: round 0's input
        params, then the output of each of the ``CHECK_ROUNDS`` rounds."""
        import jax
        if r == 0:
            self.p0 = jax.device_get(args[0]["params"])
        else:
            self.kept.append(jax.device_get(self.out))

    def enter(self, args):
        r = len(self.stamps)
        if r <= CHECK_ROUNDS:
            self._keep(r, args)
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
    """Drive ``train_ppo`` through set-up and the window, with the
    arguments of the configuration's driver. Returns the ``Rounds``
    record."""
    import jax
    from repro.core import ppo

    args, kwargs = spec.driver_of(config).trainer(config, traffic, seed)
    rounds = Rounds(seconds, spans, tracer, compiles)
    orig = ppo._make_episode_fn

    def make_episode_fn(*a, **k):
        return rounds.wrap(orig(*a, **k))

    ppo._make_episode_fn = make_episode_fn
    try:
        with spans.span("train_ppo"):
            ppo.train_ppo(*args, **kwargs)
    except WindowClosed:
        pass
    finally:
        ppo._make_episode_fn = orig
    jax.effects_barrier()
    return rounds


def program_numbers(rounds):
    kept = rounds.kept
    return {"loss": [float(k[2]) for k in kept],
            "reward_mean": [float(np.mean(k[1])) for k in kept],
            "rewards": [np.asarray(k[1], np.float64) for k in kept],
            "m0": kept[0][0]["opt"]["m"],
            "v0": kept[0][0]["opt"]["v"],
            "p0": rounds.p0,
            "p3": kept[CHECK_ROUNDS - 1][0]["params"]}


def _shape_of(x):
    """What the program is lowered from for ``x``: its shape, dtype and,
    where it was placed, its sharding (an array left where JAX put it
    lowers with none, and a sharding would change the program)."""
    import jax
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    return x


def temp_bytes(rounds):
    """Compiled temporaries of the episode program, from its own
    compile (served from the cache), lowered from the shapes of the last
    round's arguments (the same program text as from the arrays): a
    program that donates its state has deleted the arrays themselves."""
    import jax
    fn, args = rounds.last
    try:
        return int(fn.lower(*jax.tree.map(_shape_of, args)).compile()
                   .memory_analysis().temp_size_in_bytes)
    except Exception as e:  # a reading, not a result: say so and go on
        warn(f"no memory_analysis of the episode program: {e!r}")
        return 0


def reference(config, traffic, seed, dtype=None, **fault):
    """The plain reference's first ``CHECK_ROUNDS`` rounds from the same
    seed, by the configuration's driver: the numbers ``program_numbers``
    gives. ``dtype`` names the reference's arithmetic and defaults to the
    precision the configuration states; ``fault`` plants one of the
    driver's ``faults``."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or PRECISION[config["matmul_precision"]]
    params, opt, step = spec.driver_of(config).reference(
        config, traffic, seed, dtype, **fault)
    out = {"loss": [], "reward_mean": [], "rewards": [],
           "p0": jax.device_get(params)}
    for r in range(CHECK_ROUNDS):
        params, opt, loss, rew = step(params, opt, r)
        if r == 0:
            out["m0"] = jax.device_get(opt["m"])
            out["v0"] = jax.device_get(opt["v"])
        out["loss"].append(float(loss))
        out["reward_mean"].append(float(jnp.mean(rew)))
        out["rewards"].append(np.asarray(rew, np.float64))
    out["p3"] = jax.device_get(params)
    return out


def run(config, traffic, seed, seconds, spans, tracer, compiles, t_start):
    """The whole training run; returns (end_to_end, context for the
    per-layer readers, the program's numbers, extra memory bytes,
    counts)."""
    driver = spec.driver_of(config)
    rounds = run_program(config, traffic, seed, seconds, spans, tracer,
                         compiles)
    ws, we = rounds.window
    n = rounds.window_rounds
    steps = n * driver.steps_per_round(config, traffic)
    e2e = {"train_env_steps_per_s": steps / (we - ws),
           "setup_s": ws - t_start}
    ctx = {"rounds": n, "window": (ws, we),
           "flops_per_round": driver.round_flops(config, traffic),
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
