"""Online adaptation layer (repro.core.online) + the live-controller loop
fixes that rode along in the same PR.

The load-bearing pin is the default-off contract: ``online=None`` runs
LITERALLY the pre-change controller program — the two hex goldens below
were captured from the controllers BEFORE the online layer (or any of the
loop restructuring) existed, with n_max large enough that the sampled
actions sit in the interior of [1, n_max] (a saturated golden would pin
nothing). atol=0: the comparison is exact int64 bytes.

The rest: online-head determinism, the safety-rail state machine
(fallback + hysteresis), and regressions for the three loop bugs — the
monotonic run clock, exit-before-sleep termination latency, and the
health check's worker-name parsing / single byte snapshot per interval.
The live SharedLink replay is slow-marked; ``pytest -m online`` runs the
whole subsystem including it.
"""

import threading
import time

import numpy as np
import pytest

import jax

from repro.core import networks as nets
from repro.core.controller import AutoMDTController, FleetController
from repro.core.fleet import make_flow_objective
from repro.core.online import (OnlineAdapter, OnlineConfig, ReplayBuffer,
                               realized_reward)
from repro.core.simulator import CONTEXT_OBS, FLEET_OBS, ObservationSpec

pytestmark = pytest.mark.online

OBJ = ObservationSpec(context=True, fleet=True, objectives=True)

# actions of the PRE-online-layer FleetController/AutoMDTController on the
# seeded observation streams below, as int64 little-endian hex. First
# captured before the online layer touched the controllers; re-captured
# under JAX 0.9's default RNG stream (jax_threefry_partitionable=True),
# after checking that the earlier capture still reproduced exactly with
# jax_threefry_partitionable=False — only the key stream changed.
GOLD_FLEET = (
    "1000000000000000190000000000000018000000000000001a00000000000000"
    "1800000000000000140000000000000015000000000000001600000000000000"
    "2000000000000000170000000000000019000000000000001300000000000000"
    "16000000000000001c0000000000000015000000000000001100000000000000"
    "14000000000000001d000000000000001d000000000000001800000000000000"
    "1b0000000000000018000000000000001c000000000000001300000000000000"
    "1300000000000000180000000000000014000000000000001400000000000000"
    "1d000000000000001f000000000000001d000000000000002400000000000000"
    "1400000000000000140000000000000013000000000000001900000000000000"
    "2100000000000000190000000000000019000000000000001400000000000000"
    "17000000000000001b0000000000000019000000000000001500000000000000"
    "220000000000000014000000000000001f000000000000001b00000000000000"
    "1c000000000000001f0000000000000017000000000000000d00000000000000"
    "1d000000000000001800000000000000")
GOLD_AUTO = (
    "140000000000000016000000000000001a000000000000002000000000000000"
    "1e000000000000001d000000000000001a000000000000001e00000000000000"
    "150000000000000017000000000000001f000000000000001500000000000000"
    "1700000000000000190000000000000013000000000000001400000000000000"
    "1d000000000000001e00000000000000")


def _fleet_obs_stream(rng, steps=6, n_flows=3):
    for _ in range(steps):
        yield dict(
            threads=rng.integers(1, 9, (n_flows, 3)).astype(float),
            throughputs=rng.uniform(0.05, 1.0, (n_flows, 3)),
            sender_free=rng.uniform(0.1, 2.0, n_flows),
            receiver_free=rng.uniform(0.1, 2.0, n_flows),
            sender_capacity=np.full(n_flows, 2.0),
            receiver_capacity=np.full(n_flows, 2.0))


def _auto_obs_stream(rng, steps=6):
    for _ in range(steps):
        yield dict(
            threads=rng.integers(1, 9, 3).astype(float).tolist(),
            throughputs=rng.uniform(0.05, 1.0, 3).tolist(),
            sender_free=float(rng.uniform(0.1, 2.0)),
            receiver_free=float(rng.uniform(0.1, 2.0)),
            sender_capacity=2.0, receiver_capacity=2.0)


def _fleet_golden_actions(online=None):
    params = nets.policy_init(jax.random.PRNGKey(7), obs_dim=OBJ.dim,
                              act_dim=3, hidden=16)
    ctrl = FleetController(
        params, n_flows=3, n_max=400.0, bw_ref=1.0, deterministic=False,
        seed=3, obs_spec=OBJ, online=online,
        objectives=make_flow_objective(3,
                                       deadline=[30.0, np.inf, np.inf],
                                       demand=[5.0, np.inf, np.inf]))
    rng = np.random.default_rng(42)
    acts = [ctrl.step_arrays(o, t=float(s), delivered=np.full(3, 0.3 * s))
            for s, o in enumerate(_fleet_obs_stream(rng))]
    return np.stack(acts).astype(np.int64)


def test_online_none_fleet_bit_identical_golden():
    """``online=None`` (the default) must run the EXACT pre-change fleet
    program: stochastic sampling, same RNG stream, same frames — pinned
    at atol=0 (exact int64 bytes) against the pre-PR golden."""
    acts = _fleet_golden_actions(online=None)
    assert acts.tobytes().hex() == GOLD_FLEET


def test_online_none_auto_bit_identical_golden():
    """Same default-off pin for the single-flow GRU controller."""
    gparams = nets.rnn_policy_init(jax.random.PRNGKey(5),
                                   obs_dim=CONTEXT_OBS.dim, act_dim=3,
                                   hidden=16)
    auto = AutoMDTController(gparams, n_max=400, bw_ref=1.0,
                             deterministic=False, seed=9,
                             obs_spec=CONTEXT_OBS, policy="gru",
                             online=None)
    rng = np.random.default_rng(17)
    acts = [auto.step(o) for o in _auto_obs_stream(rng)]
    assert np.asarray(acts, np.int64).tobytes().hex() == GOLD_AUTO


def test_online_enabled_diverges_from_frozen_only_after_warmup():
    """The knob must actually do something — but not before the rails
    allow it: during warmup the online controller's actions are the
    frozen actions bit-for-bit (same RNG stream), and the adapter is
    feeding its buffer the whole time."""
    cfg = OnlineConfig(warmup=2, step=4.0, explore=1.0)
    frozen = _fleet_golden_actions(online=None)
    adapted = _fleet_golden_actions(online=cfg)
    # steps 0..1 settle rewards for fed=1,2; engagement flips at fed=2,
    # so the first step that may diverge is step 2's adjust
    assert np.array_equal(adapted[:2], frozen[:2])
    assert adapted.shape == frozen.shape
    assert (adapted >= 1).all() and (adapted <= 400).all()


def test_online_head_deterministic_given_stream():
    """Bit-determinism of the online head: two identically-configured
    controllers fed the same observation stream produce identical actions
    and identical residuals — including the seeded epsilon dither."""
    cfg = OnlineConfig(warmup=1, step=3.0, explore=0.5, epsilon=0.25,
                       seed=11)

    def run():
        params = nets.policy_init(jax.random.PRNGKey(2),
                                  obs_dim=FLEET_OBS.dim, act_dim=3,
                                  hidden=16)
        ctrl = FleetController(params, n_flows=2, n_max=64, bw_ref=1.0,
                               deterministic=False, seed=5,
                               obs_spec=FLEET_OBS, online=cfg)
        rng = np.random.default_rng(3)
        acts = [ctrl.step_arrays(o)
                for o in _fleet_obs_stream(rng, steps=10, n_flows=2)]
        return np.stack(acts), ctrl._online.residual.copy()

    acts_a, res_a = run()
    acts_b, res_b = run()
    assert np.array_equal(acts_a, acts_b)
    assert np.array_equal(res_a, res_b)
    assert np.any(res_a != 0.0)   # the head actually moved off frozen


def test_replay_buffer_ring_semantics():
    buf = ReplayBuffer(4, ctx_dim=2)
    assert len(buf) == 0
    for i in range(6):
        buf.push(np.full((1, 2), float(i)), np.zeros((1, 3)),
                 np.zeros((1, 3), int), [float(i)])
    assert len(buf) == 4   # oldest two aged out
    frames, _, _, rewards = buf.view()
    assert set(rewards.tolist()) == {2.0, 3.0, 4.0, 5.0}
    assert frames.shape == (4, 2)


def test_realized_reward_matches_utility_form():
    tps = np.array([[1.0, 0.5, 0.25]])
    n = np.array([[1.0, 2.0, 3.0]])
    want = (1.0 / 1.02 + 0.5 / 1.02 ** 2 + 0.25 / 1.02 ** 3)
    assert np.allclose(realized_reward(tps, n), [want])
    assert np.allclose(realized_reward(tps, n, weights=[2.0]), [2 * want])


# ---------------------------------------------------------------------------
# Safety rails: fallback + hysteresis
# ---------------------------------------------------------------------------

def _feed(adapter, frames, frozen, reward_tps):
    """One control interval: decide, then settle it with telemetry whose
    realized reward is sum(reward_tps / 1.02) (threads=1)."""
    applied = adapter.adjust(frames, frozen)
    adapter.observe_outcome(np.asarray([reward_tps], float),
                            np.ones((1, 3)))
    return applied


def test_safety_rails_fallback_and_hysteresis():
    cfg = OnlineConfig(warmup=1, fallback=-0.2, re_engage=-0.05,
                       cooldown=3, beta=0.5, step=2.0, explore=0.0)
    ad = OnlineAdapter(cfg, n_flows=1, n_max=32)
    frames = np.ones((1, 4))
    frozen = np.full((1, 3), 8.0)

    # warmup: frozen passthrough, then the good reference engages the head
    applied = _feed(ad, frames, frozen, [1.0, 1.0, 1.0])
    assert ad.mode == "on" and np.array_equal(applied, frozen.astype(int))

    # engaged intervals whose realized reward collapses: the advantage
    # estimate degrades below ``fallback`` -> snap back to frozen
    for _ in range(4):
        if ad.mode != "on":
            break
        _feed(ad, frames, frozen, [0.0, 0.0, 0.0])
    assert ad.mode == "off"
    assert ad.n_fallbacks == 1
    assert np.all(ad.residual == 0.0)   # residuals zeroed on fallback

    # disengaged: frozen passthrough, and NO re-engage inside the cooldown
    # even though the world recovered (the hysteresis band's lower lip)
    for i in range(2):
        applied = _feed(ad, frames, frozen, [1.0, 1.0, 1.0])
        assert np.array_equal(applied, frozen.astype(int))
        assert ad.mode == "off", f"re-engaged after only {i + 1} steps"

    # past the cooldown the relaxing estimate clears ``re_engage`` and the
    # head probes again
    for _ in range(16):
        _feed(ad, frames, frozen, [1.0, 1.0, 1.0])
        if ad.mode == "on":
            break
    assert ad.mode == "on"
    assert ad.n_fallbacks == 1          # one clean cycle, no flapping


def test_online_config_validates_hysteresis_band():
    with pytest.raises(ValueError):
        OnlineConfig(fallback=-0.05, re_engage=-0.25)
    with pytest.raises(ValueError):
        OnlineConfig(warmup=0)


# ---------------------------------------------------------------------------
# Loop bugfix regressions: run clock, termination latency, health check
# ---------------------------------------------------------------------------

class _FakeEngine:
    """Minimal live-engine stand-in for the run-loop tests (the controller
    step is stubbed, so observe() can stay skeletal)."""

    def __init__(self, total=10 ** 9):
        self.total = total
        self.b = 0
        self.alive = True
        self.steers = 0
        self.byte_reads = 0

    def observe(self):
        return {"threads": (1, 1, 1), "throughputs": (0.1, 0.1, 0.1)}

    def bytes_written(self):
        self.byte_reads += 1
        return self.b

    def done(self):
        return self.b >= self.total

    def set_concurrency(self, n):
        self.steers += 1

    def wait(self, seconds):   # AutoMDTController.run contract
        time.sleep(seconds)

    def close(self):
        self.alive = False


def _stub_ctrl(n_flows=2):
    ctrl = FleetController(None, n_flows=n_flows, n_max=10, bw_ref=1.0)
    ctrl._step_ts = []

    def step(obs, active=None, t=0.0, delivered=None):
        ctrl._step_ts.append(t)
        return [(1, 1, 1)] * len(obs)
    ctrl.step = step
    return ctrl


def test_run_clock_survives_wall_clock_step(monkeypatch):
    """An NTP step on the wall clock mid-run must never run the trace (or
    the objective-feature ``t``) backwards: the run loops ride
    ``time.monotonic``, not ``time.time`` — regression for the old
    wall-clock run clock."""
    wall = {"t": 10_000.0}
    monkeypatch.setattr(time, "time", lambda: wall.pop("t", 9_000.0))
    # ^ first call 10000.0, every later call 9000.0 — a huge backward step
    ctrl = _stub_ctrl()
    engines = [_FakeEngine(), _FakeEngine()]
    trace = ctrl.run(engines, interval=0.01, max_steps=4)
    ts = [t for t, _, _ in trace]
    assert len(ts) == 4
    assert all(b >= a for a, b in zip(ts, ts[1:])), ts
    assert all(t >= 0.0 for t in ts)
    # the t the objective features see never regresses either
    st = ctrl._step_ts
    assert all(b >= a for a, b in zip(st, st[1:])), st

    # single-flow loop, same property
    auto = AutoMDTController(None, n_max=10, bw_ref=1.0)
    auto.step = lambda obs: (1, 1, 1)
    e = _FakeEngine()
    atrace = auto.run(e, interval=0.01, max_steps=4)
    ats = [t for t, _, _ in atrace]
    assert all(b >= a for a, b in zip(ats, ats[1:])), ats
    assert all(t >= 0.0 for t in ats)


def test_run_returns_promptly_when_already_settled():
    """Exit conditions are checked BEFORE the interval sleep: a fleet
    that is already done (or closed) at entry returns without burning a
    multi-second interval — regression for the sleep-then-check loop."""
    ctrl = _stub_ctrl()
    done = [_FakeEngine(total=0), _FakeEngine(total=0)]   # done() at entry
    t0 = time.monotonic()
    trace = ctrl.run(done, interval=5.0)
    assert time.monotonic() - t0 < 1.0
    assert trace == []

    closed = [_FakeEngine(), _FakeEngine()]
    for e in closed:
        e.close()
    t0 = time.monotonic()
    assert ctrl.run(closed, interval=5.0) == []
    assert time.monotonic() - t0 < 1.0


def test_run_sleep_aborts_when_fleet_settles_mid_interval():
    """The interval sleep is abort-aware: a fleet torn down mid-sleep ends
    the interval within the settle-poll slice, not at the full interval."""
    ctrl = _stub_ctrl()
    engines = [_FakeEngine(), _FakeEngine()]

    def teardown():
        time.sleep(0.2)
        for e in engines:
            e.close()
    th = threading.Thread(target=teardown)
    t0 = time.monotonic()
    th.start()
    ctrl.run(engines, interval=10.0)
    elapsed = time.monotonic() - t0
    th.join()
    assert elapsed < 3.0, f"burned the whole interval: {elapsed:.1f}s"


def test_health_check_ignores_foreign_workers():
    """A shared registry may carry workers that are NOT this controller's
    flows — a ``flowctl`` supervisor, an out-of-range ``flow99`` from a
    previous (larger) fleet. Neither may crash the loop (the old code
    ``int(w[4:])``-parsed every key) nor mask a live flow."""
    from repro.runtime import HeartbeatRegistry
    ctrl = _stub_ctrl()
    reg = HeartbeatRegistry()
    reg.beat("flowctl", 0, 1.0)     # foreign: no digits — must be skipped
    reg.beat("flow99", 0, 1.0)      # foreign: beyond this fleet's range
    reg.beat("flow0x", 0, 1.0)      # foreign: trailing junk (fullmatch)
    e0, e1 = _FakeEngine(), _FakeEngine()

    def pump():
        for _ in range(40):
            e0.b += 1000
            e1.b += 1000
            time.sleep(0.01)
    th = threading.Thread(target=pump)
    th.start()
    ctrl.run([e0, e1], interval=0.05, max_steps=4, registry=reg,
             dead_after=10.0)
    th.join()
    # both real flows beat; the foreign keys survive untouched
    snap = reg.snapshot()
    assert {"flow0", "flow1"}.issubset(snap)
    assert "flowctl" in snap and "flow99" in snap
    assert e0.steers == e1.steers == 4   # nobody was masked


def test_run_takes_one_byte_snapshot_per_interval():
    """ONE ``bytes_written`` pass per control interval feeds the health
    check, the termination sum, and ``delivered`` — regression for the
    three separate per-engine loops the old run body made."""
    from repro.runtime import HeartbeatRegistry
    ctrl = _stub_ctrl()
    engines = [_FakeEngine(), _FakeEngine()]
    ctrl.run(engines, interval=0.01, max_steps=3, total_bytes=10 ** 12,
             registry=HeartbeatRegistry())
    # 3 full iterations + the exiting check = 4 snapshots, each ONE read
    assert all(e.byte_reads == 4 for e in engines), \
        [e.byte_reads for e in engines]


# ---------------------------------------------------------------------------
# Live replay: the online layer on a real SharedLink fleet
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_online_adapts_on_live_shared_link():
    """The full live loop: FleetController(online=...) drives real engines
    contending on a SharedLink — the adapter's buffer fills from live
    telemetry, the head engages after warmup, and every applied action
    stays in [1, n_max]."""
    from repro.transfer import SharedLink, SyntheticSource, ChecksumSink
    MB = 1 << 20
    n_flows, n_max = 2, 16
    link = SharedLink(aggregate_bps=(None, 4 * MB, None))
    for f in range(n_flows):
        link.attach(SyntheticSource(1 << 40, chunk_bytes=64 * 1024, seed=f),
                    ChecksumSink(), initial_concurrency=(2, 2, 2),
                    n_max=n_max, metric_interval=0.1)
    params = nets.policy_init(jax.random.PRNGKey(0), obs_dim=FLEET_OBS.dim,
                              act_dim=3, hidden=16, action_scale=n_max / 4)
    cfg = OnlineConfig(warmup=1, step=2.0, max_residual=8.0, explore=0.5)
    ctrl = FleetController(params, n_flows=n_flows, n_max=n_max,
                           bw_ref=4.0 * MB, obs_spec=FLEET_OBS,
                           deterministic=True, interval=0.25, online=cfg)
    try:
        trace = ctrl.run(link, interval=0.25, max_steps=8)
    finally:
        link.close()
    assert len(trace) == 8
    ad = ctrl._online
    assert ad._fed >= 7                 # every interval settled a decision
    assert len(ad.buffer) > 0           # live transitions recorded
    assert ad.mode in ("on", "off")     # left warmup
    for _, threads, _ in trace:
        for n3 in threads:
            assert all(1 <= n <= n_max for n in n3), threads
