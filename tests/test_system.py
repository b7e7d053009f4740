"""End-to-end behaviour tests for the full system: controllers vs baselines
on live engines, AutoMDT-driven training, serving, and the production
controller loop — the paper's architecture as a framework feature."""

import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (AutoMDTController, GlobusController, MarlinOptimizer,
                        PPOConfig, train_ppo, make_env_params, SimEnv, explore)
from repro.core.simulator import env_reset, env_step, observe
from repro.transfer import (TransferEngine, SyntheticSource, ChecksumSink,
                            StageThrottle)

MB = 1 << 20


def _train_policy(p, seed=0, episodes=1200, n_max=50):
    env = SimEnv(p, seed=seed)
    env.reset()
    ex = explore(env.probe, n_samples=150, n_max=n_max, seed=seed)
    res = train_ppo(p, PPOConfig(max_episodes=episodes, n_envs=32,
                                 action_scale=n_max / 4, seed=seed),
                    r_max=ex.r_max)
    return res, ex


def _obs_dict(p, st):
    return {"threads": list(np.asarray(st.threads)),
            "throughputs": list(np.asarray(st.throughputs)),
            "sender_free": float(p.cap[0] - st.buffers[0]),
            "receiver_free": float(p.cap[1] - st.buffers[1]),
            "sender_capacity": float(p.cap[0]),
            "receiver_capacity": float(p.cap[1])}


def test_automdt_beats_marlin_and_globus_in_sim():
    """Paper Fig. 5 in miniature: on a read-bottleneck env, AutoMDT reaches
    higher utility faster than Marlin; Globus's static config underutilizes."""
    p = make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                        n_max=50)
    res, ex = _train_policy(p)
    ctrl = AutoMDTController(res.params["policy"], n_max=50,
                             bw_ref=float(ex.bandwidth.max()),
                             deterministic=True)

    def run(controller, steps=30):
        st = env_reset(p, jax.random.PRNGKey(7))
        delivered = []
        for _ in range(steps):
            o = _obs_dict(p, st)
            if isinstance(controller, AutoMDTController):
                n = controller.step(o)
            else:
                n = controller.update(o["throughputs"])
            st, _, _ = env_step(p, st, jnp.asarray(n, jnp.float32))
            delivered.append(float(st.throughputs[2]))
        return np.asarray(delivered)

    auto = run(ctrl)
    marlin = run(MarlinOptimizer(n_max=50))
    globus = run(GlobusController())
    # AutoMDT saturates the 1 Gbps bottleneck quickly...
    assert auto[5:].mean() > 0.9, auto
    # ...and beats both baselines on delivered bytes
    assert auto.sum() > marlin.sum(), (auto.sum(), marlin.sum())
    assert auto.sum() > globus.sum() * 1.5, (auto.sum(), globus.sum())
    # Globus's static 4 threads x 80 Mbps leaves the link underutilized
    assert globus[5:].mean() < 0.5


def test_automdt_convergence_speed_vs_marlin():
    """Paper Fig. 3: time-to-bottleneck-utilization. AutoMDT must reach 95%
    utilization at least 2x faster than Marlin."""
    p = make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                        n_max=50)
    res, ex = _train_policy(p, seed=1)
    ctrl = AutoMDTController(res.params["policy"], n_max=50,
                             bw_ref=float(ex.bandwidth.max()),
                             deterministic=True)

    def first_hit(controller, steps=60):
        st = env_reset(p, jax.random.PRNGKey(11))
        for i in range(steps):
            o = _obs_dict(p, st)
            n = (controller.step(o) if isinstance(controller, AutoMDTController)
                 else controller.update(o["throughputs"]))
            st, _, _ = env_step(p, st, jnp.asarray(n, jnp.float32))
            if float(st.throughputs[2]) >= 0.95:
                return i + 1
        return steps

    t_auto = first_hit(ctrl)
    t_marlin = first_hit(MarlinOptimizer(n_max=50))
    assert t_auto * 2 <= t_marlin, (t_auto, t_marlin)


def test_controller_drives_real_engine_to_completion():
    """Production phase (§IV-F) against the live threaded engine."""
    p = make_env_params(tpt=[0.08, 0.16, 0.2], bw=[1, 1, 1], cap=[2, 2],
                        n_max=32)
    res, ex = _train_policy(p, seed=2, episodes=800, n_max=32)
    ctrl = AutoMDTController(res.params["policy"], n_max=32,
                             bw_ref=float(ex.bandwidth.max()),
                             deterministic=True)
    total = 24 * MB
    src = SyntheticSource(total, chunk_bytes=128 * 1024)
    sink = ChecksumSink()
    # same shape as the sim env, scaled: per-thread 0.8/1.6/2.0 MB/s, 10 MB/s caps
    eng = TransferEngine(
        src, sink, sender_buf=4 * MB, receiver_buf=4 * MB,
        throttles=(StageThrottle(10 * MB, int(0.8 * MB)),
                   StageThrottle(10 * MB, int(1.6 * MB)),
                   StageThrottle(10 * MB, int(2.0 * MB))),
        initial_concurrency=(1, 1, 1), n_max=32, metric_interval=0.3)
    trace = ctrl.run(eng, total_bytes=total, interval=0.3, max_steps=120)
    eng.close()
    assert sink.nbytes == total
    # controller raised read concurrency above write (read is the bottleneck)
    final_threads = trace[-1][1]
    assert final_threads[0] > final_threads[2], trace[-1]


def test_training_driver_end_to_end(tmp_path):
    """~100M-family (smollm) reduced config: tuned input pipeline +
    fault-tolerant loop; loss decreases. The corpus is uniform random
    tokens, so per-batch losses scatter by ~0.04 around a trend far
    smaller than that at 30 warm-up steps: whether a head/tail window
    comparison passes depends on the PRNG stream, not on learning. The
    trend is read instead on one fixed held-out batch, scored with the
    initial and the final params — no batch-sampling noise, and a drop
    only a parameter update can make."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import init_state
    from repro.launch.train import train
    from repro.models import get_model
    cfg = get_smoke_config("smollm-135m")
    final, info = train(cfg, steps=30, batch=4, seq=64,
                        ckpt_dir=str(tmp_path / "ckpt"), controller="globus",
                        log_every=0)
    losses = np.asarray(info["losses"])
    assert len(losses) == 30
    assert np.isfinite(losses).all(), losses
    rows = np.random.default_rng(12345).integers(0, cfg.vocab, (32, 65),
                                                 dtype=np.int32)
    held = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
    loss_fn = get_model(cfg).loss_fn
    before = float(loss_fn(init_state(cfg, jax.random.PRNGKey(0))["params"],
                           held)[0])
    after = float(loss_fn(final["params"], held)[0])
    assert after < before, (before, after)
    assert info["report"].checkpoints >= 1


def test_serving_driver_end_to_end():
    from repro.configs import get_smoke_config
    from repro.launch.serve import serve
    cfg = get_smoke_config("deepseek-7b")
    toks, stats = serve(cfg, batch=2, prompt_len=16, gen=8)
    assert toks.shape == (2, 8)
    assert stats["tok_per_s"] > 0
