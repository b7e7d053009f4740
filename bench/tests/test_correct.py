"""``correct`` on a small copy of the cell, on the CPU: a sound run passes,
also with its train state donated, and so does a second deployment added
as files; the float8 control and each fault the cell can have, planted
under the timed path, fail. The harness's look for a chip is skipped;
everything else is the run as ``bench/run.py`` makes it."""

import json
import shutil

import pytest

import run as bench_run
from harness import check, spec, train

CELL = "single_flow_train.read"
SMALL = {"n_envs": 16}
SEED = 2 ** 31 + 99
LOAD_TRAFFIC = spec.load_traffic
LOAD_DRIVER = spec.load_driver


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


def run_small(monkeypatch, capsys, seconds=1.0, cell=CELL):
    monkeypatch.setattr(spec, "load_traffic", small_traffic)
    rc = bench_run.main(["--workload", cell, "--seed", str(SEED),
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


def test_a_donated_train_state_is_still_correct(monkeypatch, capsys):
    """What donating the train state does under the timed path: after each
    dispatch the input state's device buffers are deleted. The trainer's
    own best-param copy still reads round r's params after round r+1 is
    dispatched, so a host copy of them stands in their place for it; the
    harness reads none of the deleted buffers, and ``temp_bytes`` lowers
    the program from their shapes."""
    import jax
    from repro.core import ppo
    orig = ppo._make_episode_fn

    def make(*a, **k):
        fn = orig(*a, **k)

        def episode(state, *rest):
            out = fn(state, *rest)
            params = jax.device_get(state["params"])
            for leaf in jax.tree.leaves(state):
                leaf.delete()
            state["params"] = params
            return out
        episode.lower = fn.lower
        return episode
    monkeypatch.setattr(ppo, "_make_episode_fn", make)
    temp = []
    temp_bytes = train.temp_bytes
    monkeypatch.setattr(train, "temp_bytes",
                        lambda rounds: temp.append(temp_bytes(rounds))
                        or temp[-1])
    result = run_small(monkeypatch, capsys)
    assert result["correct"], result["checks"]
    assert len(temp) == 1 and temp[0] > 0


def test_a_deployment_is_added_with_files_and_entries_only(
        monkeypatch, capsys, tmp_path):
    """A second driver, added as a file (the single-flow driver under
    another name), with a configuration and a cell that name it: the
    harness runs it to ``correct`` as it stands."""
    (tmp_path / "drivers").mkdir()
    shutil.copy(spec.BENCH_DIR / "drivers" / "single_flow.py",
                tmp_path / "drivers" / "second_flow.py")
    bench = spec.load_benchmark()
    base = spec.find_cell(bench, CELL)
    config = dict(spec.load_config(bench, base["config"]),
                  name="second_deployment", driver="second_flow")
    (tmp_path / "second_deployment.json").write_text(json.dumps(config))
    bench["configs"].append(
        dict(bench["configs"][0], name="second_deployment",
             file=str(tmp_path / "second_deployment.json")))
    cell = dict(base, name="second_deployment.read",
                config="second_deployment")
    bench["workloads"].append(cell)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if CELL in m.get("workloads", []):
                m["workloads"].append(cell["name"])
    loaded = []

    def load_driver(name):
        loaded.append(name)
        return LOAD_DRIVER(name, bench_dir=tmp_path)
    monkeypatch.setattr(spec, "load_benchmark", lambda root=None: bench)
    monkeypatch.setattr(spec, "load_driver", load_driver)
    result = run_small(monkeypatch, capsys, cell=cell["name"])
    assert result["correct"], result["checks"]
    assert set(loaded) == {"second_flow"}
    assert set(result["metrics"]) == {"train_env_steps_per_s", "setup_s"}


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


def test_each_fault_the_driver_names_is_not_correct():
    """Each fault that ``bench/calibrate.py`` reads its upper readings
    from, planted in the reference put in the program's place, fails."""
    bench = spec.load_benchmark()
    c = spec.find_cell(bench, CELL)
    config = spec.load_config(bench, c["config"])
    tr = small_traffic(c["traffic"])
    ref = train.reference(config, tr, SEED)
    faults = spec.driver_of(config).faults(config, tr)
    assert faults
    for name, kw in faults.items():
        bad = train.reference(config, tr, SEED, **kw)
        ok, checks = check.judge(check.train_numbers(bad, ref), tr["limits"])
        assert not ok, (name, checks)
