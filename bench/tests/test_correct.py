"""``correct`` on a small copy of the cell, on the CPU: a sound run passes;
the float8 control and each fault the cell can have, planted under the
timed path, fail. The harness's look for a chip is skipped; everything
else is the run as ``bench/run.py`` makes it."""

import json

import pytest

import run as bench_run
from harness import check, spec, train

CELL = "single_flow_train.read"
SMALL = {"n_envs": 16}
SEED = 2 ** 31 + 99
LOAD_TRAFFIC = spec.load_traffic


@pytest.fixture(autouse=True)
def fresh_traces():
    """The program's module-level jits keep their traces: drop them so a
    planted fault is traced in, and again so it does not outlive its
    test."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def small_traffic(name):
    return dict(LOAD_TRAFFIC(name), **SMALL)


def run_small(monkeypatch, capsys, seconds=1.0):
    monkeypatch.setattr(spec, "load_traffic", small_traffic)
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                         "--seconds", str(seconds), "--trace", "0"],
                        need_chip=False)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    return result


def test_a_sound_run_is_correct(monkeypatch, capsys):
    result = run_small(monkeypatch, capsys)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


def _state_unchanged(monkeypatch):
    from repro.core import ppo
    orig = ppo._make_episode_fn

    def make(*a, **k):
        fn = orig(*a, **k)

        def episode(state, *rest):
            _, rewards, loss = fn(state, *rest)
            return state, rewards, loss
        return episode
    monkeypatch.setattr(ppo, "_make_episode_fn", make)


def _half_batch(monkeypatch):
    from repro.core import ppo
    orig = ppo._loss

    def loss(params, batch, cfg):
        n = batch[0].shape[0] // 2
        return orig(params, tuple(x[:n] for x in batch), cfg)
    monkeypatch.setattr(ppo, "_loss", loss)


def _reward_altered(monkeypatch):
    """Every reward 1% larger where the environment produces it."""
    from repro.core import simulator
    orig = simulator.utility
    monkeypatch.setattr(simulator, "utility",
                        lambda t, n, k=1.02: orig(t, n, k=k) * 1.01)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_batch": _half_batch,
          "reward_altered": _reward_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_training_fault_is_not_correct(monkeypatch, capsys, fault):
    FAULTS[fault](monkeypatch)
    assert not run_small(monkeypatch, capsys)["correct"]


def test_the_float8_control_is_not_correct():
    bench = spec.load_benchmark()
    c = spec.find_cell(bench, CELL)
    config = spec.load_config(bench, c["config"])
    tr = small_traffic(c["traffic"])
    ref = train.reference(config, tr, SEED)
    low = train.reference(config, tr, SEED, "float8")
    ok, checks = check.judge(check.train_numbers(low, ref), tr["limits"])
    assert not ok, checks
