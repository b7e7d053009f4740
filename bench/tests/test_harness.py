"""The harness's pieces: lookup by name, seeded inputs, the FLOP count, the
trace reduction on a small recorded trace, the window's wait for the
device, and the refusal to run without a chip."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from harness import flops, spec, train
from harness.trace import DeviceTrace, Spans

BENCH = spec.BENCH_DIR
ROOT = spec.ROOT


def test_every_cell_finds_its_pieces():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        config = spec.load_config(bench, cell["config"])
        traffic = spec.load_traffic(cell["traffic"])
        assert traffic["limits"]
        assert all(v > 0 for v in traffic["limits"].values())
        assert config["matmul_precision"] in train.PRECISION
        for kind in ("end_to_end", "per_layer"):
            assert spec.metrics_for(bench, cell["name"], kind)
        driver = spec.driver_of(config)
        assert all(callable(getattr(driver, f))
                   for f in spec.DRIVER_FUNCTIONS)
        args, kwargs = driver.trainer(config, traffic, 2 ** 31 + 1)
        assert isinstance(args, tuple) and isinstance(kwargs, dict)
        steps = driver.steps_per_round(config, traffic)
        assert isinstance(steps, int) and steps > 0
        assert driver.round_flops(config, traffic) > 0
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))


@pytest.mark.parametrize("driver", ["no_such_driver", None])
def test_a_configuration_without_a_known_driver_is_an_error(driver):
    bench = spec.load_benchmark()
    config = spec.load_config(bench, bench["configs"][0]["name"])
    if driver is None:
        del config["driver"]
    else:
        config["driver"] = driver
    with pytest.raises(KeyError, match="driver"):
        spec.driver_of(config)
    with pytest.raises(KeyError, match="driver"):
        train.reference(config, spec.load_traffic(
            bench["workloads"][0]["traffic"]), 1)


def test_a_driver_without_all_its_functions_is_an_error(tmp_path):
    (tmp_path / "drivers").mkdir()
    (tmp_path / "drivers" / "half.py").write_text(
        "def trainer(config, traffic, seed):\n    return (), {}\n")
    with pytest.raises(KeyError, match="lacks"):
        spec.load_driver("half", bench_dir=tmp_path)


@pytest.mark.parametrize("placed", [False, True])
def test_shapes_lower_the_same_program_as_the_arrays(placed):
    """``temp_bytes`` lowers from the arguments' shapes, which a donated
    state still has: the program text is the one the arrays give."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((4, 8))
    if placed:
        x = jax.device_put(x, jax.devices()[0])
    f = jax.jit(lambda s: {"w": s["w"] @ s["w"].T})
    want = f.lower({"w": x}).as_text()
    x.delete()
    assert f.lower({"w": train._shape_of(x)}).as_text() == want


def test_unknown_names_are_errors():
    bench = spec.load_benchmark()
    with pytest.raises(KeyError):
        spec.find_cell(bench, "no_such_cell")
    with pytest.raises(KeyError):
        spec.load_config(bench, "no_such_config")
    with pytest.raises(FileNotFoundError):
        spec.load_traffic("no_such_traffic")
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v0 imaginary")
    assert spec.load_peaks("TPU v5 lite")["flops_bf16"] == 197e12


def test_same_seed_same_inputs():
    """The inputs are the weights and the episodes' keys, both drawn from
    the seed: the reference made twice from one seed reads the same, and
    from another seed differs."""
    bench = spec.load_benchmark()
    cell = bench["workloads"][0]
    config = spec.load_config(bench, cell["config"])
    traffic = dict(spec.load_traffic(cell["traffic"]), n_envs=2)
    seed = 2 ** 31 + 12345
    a, b, c = (train.reference(config, traffic, s)
               for s in (seed, seed, seed + 1))
    assert a["loss"] == b["loss"] and a["reward_mean"] == b["reward_mean"]
    np.testing.assert_array_equal(a["p3"]["policy"]["embed"]["w"],
                                  b["p3"]["policy"]["embed"]["w"])
    assert a["loss"] != c["loss"]


def test_flops_against_a_hand_count():
    # policy: 8*256 + 3 blocks * 2 * 256*256 + 256*3 multiply-adds
    assert flops.policy_forward(8, 256) == 2 * (2048 + 393216 + 768)
    # value: 8*256 + 2 blocks * 2 * 256*256 + 256
    assert flops.value_forward(8, 256) == 2 * (2048 + 262144 + 256)
    # the single-flow cell: 1024 envs x 10 steps, 4 epochs
    total = flops.round_flops(8, 256, 10240, 4)
    assert total == 10240 * (792064 + 4 * 3 * (792064 + 528896))
    assert 0.17e12 < total < 0.171e12


def _write_trace(tmp_path):
    """A two-round window: episode runs at [0,100) and [120,200) ms with a
    small op of another program at [105,110) ms; host spans around a draw
    in the gap."""
    from jax.profiler import ProfileData
    ms = 1_000_000_000  # picoseconds
    txt = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {100 * ms} }}
    events {{ metadata_id: 2 offset_ps: {105 * ms} duration_ps: {5 * ms} }}
    events {{ metadata_id: 1 offset_ps: {120 * ms} duration_ps: {80 * ms} }}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: {60 * ms} }}
    events {{ metadata_id: 4 offset_ps: {60 * ms} duration_ps: {40 * ms} }}
    events {{ metadata_id: 5 offset_ps: {105 * ms} duration_ps: {5 * ms} }}
    events {{ metadata_id: 3 offset_ps: {120 * ms} duration_ps: {80 * ms} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_episode(3)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_pad(1)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "scatter.2" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "copy.3" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {220 * ms} }}
    events {{ metadata_id: 2 offset_ps: {110 * ms} duration_ps: {8 * ms} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.draw" }} }}
}}
"""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    return tmp_path


def test_trace_reduction_on_a_small_trace(tmp_path):
    t = DeviceTrace(str(_write_trace(tmp_path)))
    assert t.window_s == pytest.approx(0.220)
    assert t.busy_s() == pytest.approx(0.185)
    mods, _ = t.modules("jit_episode")
    assert [round(e - s, 6) for s, e in mods] == [0.1, 0.08]
    # 20 ms between the runs, 5 ms of it busy with another program
    assert t.idle_between("jit_episode") == [pytest.approx(0.015)]
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.14)]
    names = [n for n, _ in b["idle_gaps"]]
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) == pytest.approx(0.035)
    assert "draw" in names and "other" in names


def test_readers_on_the_small_trace(tmp_path):
    from types import SimpleNamespace
    t = DeviceTrace(str(_write_trace(tmp_path)))
    ctx = SimpleNamespace(trace=t, program="jit_episode", rounds=2,
                          window=(0.0, 4.0), flops_per_round=1e12,
                          peaks={"flops_bf16": 197e12})
    read = spec.load_reader
    assert read("train.episode_ms")(ctx) == pytest.approx(90.0)
    assert read("train.host_gap_ms")(ctx) == pytest.approx(15.0)
    assert read("train.mfu")(ctx) == pytest.approx(100 * 2e12 / (4 * 197e12))
    ctx.program = "jit_other"
    assert read("train.episode_ms")(ctx) is None
    assert read("train.host_gap_ms")(ctx) is None


def test_the_window_waits_for_the_device():
    """A trainer that never waits for its rounds: the window still holds
    only rounds the device has finished, so the rate is the rate of
    rounds done, not of rounds dispatched."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(0, 20, lambda i, y: jnp.tanh(y @ y), x)

    x = jnp.eye(384) * 0.5
    jax.block_until_ready(work(x))
    t = time.perf_counter()
    for _ in range(3):
        x = jax.block_until_ready(work(x))
    per_round = (time.perf_counter() - t) / 3

    def episode(state, key):
        return {"params": work(state["params"])}, key, key

    rounds = train.Rounds(3 * per_round, Spans())
    ep = rounds.wrap(episode)
    state = {"params": x}
    with pytest.raises(train.WindowClosed):
        for _ in range(10_000):  # the host loop runs ahead of the device
            state, _, _ = ep(state, 0)
            time.sleep(per_round / 10)
    ws, we = rounds.window
    assert rounds.window_rounds >= 1
    assert we - ws >= 0.7 * per_round * rounds.window_rounds


def test_a_host_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "single_flow_train.read", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_file_keys():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    assert all(c["chips"] == 1 for c in bench["workloads"])
    assert len(json.dumps(bench)) < 64 * 1024
