"""``correct`` on a small copy of the multi-link cell, on the CPU: the
topology driver's trainer, run as ``bench/run.py`` runs it, agrees with
``bench/ref/topology.py``; a program whose links give every flow their
whole capacity fails, and so do the float8 control and each fault the
driver names, planted in the reference put in the program's place. The
graph is the cell's own (12 flows over 8 links); only ``n_envs`` is cut."""

import json

import pytest

import run as bench_run
from harness import check, flops, spec, train

CELL = "dtn_mesh_train.12pair"
SMALL = {"n_envs": 8}
SEED = 2 ** 31 + 99
LOAD_TRAFFIC = spec.load_traffic


@pytest.fixture(autouse=True)
def fresh_traces():
    """Drop the program's module-level traces, so a fault planted in the
    program is traced in and does not outlive its test."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def small_traffic(name):
    return dict(LOAD_TRAFFIC(name), **SMALL)


def cell_pieces():
    bench = spec.load_benchmark()
    c = spec.find_cell(bench, CELL)
    return spec.load_config(bench, c["config"]), small_traffic(c["traffic"])


def run_small(monkeypatch, capsys):
    monkeypatch.setattr(spec, "load_traffic", small_traffic)
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                         "--seconds", "1", "--trace", "0"], need_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_multi_link_cell_is_correct(monkeypatch, capsys):
    result = run_small(monkeypatch, capsys)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_env_steps_per_s", "setup_s"}


def test_the_driver_trains_twelve_flows_on_the_topology_path():
    """``train_ppo`` gets a ``Workload`` whose topology is the 8-link graph
    in every env (each flow on 2 links, each link under 3 flows), 12 flows
    and the topology observation; a round counts n_envs x max_steps
    world-steps and its FLOPs count 12 update rows per world-step."""
    from repro.core.simulator import TOPOLOGY_OBS
    config, traffic = cell_pieces()
    driver = spec.driver_of(config)
    _, kwargs = driver.trainer(config, traffic, SEED)
    cfg, topo = kwargs["cfg"], kwargs["workload"].topology
    assert (cfg.n_flows, cfg.obs_spec, cfg.n_envs) == (12, TOPOLOGY_OBS, 8)
    assert topo.graph.bw.shape == (8, 8, 1, 3)
    assert topo.paths.onpath.shape == (8, 1, 12, 8)
    assert (topo.paths.onpath.sum(axis=-1) == 2).all()
    assert (topo.paths.onpath.sum(axis=-2) == 3).all()
    assert driver.steps_per_round(config, traffic) == 8 * 10
    assert driver.obs_dim(config) == TOPOLOGY_OBS.dim
    assert (driver.round_flops(config, traffic)
            == flops.round_flops(TOPOLOGY_OBS.dim, 256, 8 * 10 * 12, 4))


def test_a_program_whose_links_ignore_the_split_is_not_correct(
        monkeypatch, capsys):
    """Every flow on a link given the link's whole capacity, planted in
    the program's per-link solve: each flow's rates are solved as if it
    were alone on its path."""
    import jax
    import jax.numpy as jnp
    from repro.core import topology
    orig = topology._topology_substep_rates

    def no_split(params, graph, paths, threads, *args, **kwargs):
        n = threads.shape[0]

        def alone(i):
            mine = threads * (jnp.arange(n) == i)[:, None]
            return orig(params, graph, paths, mine, *args, **kwargs)[:, i]
        return jnp.moveaxis(jax.vmap(alone)(jnp.arange(n)), 0, 1)
    monkeypatch.setattr(topology, "_topology_substep_rates", no_split)
    assert not run_small(monkeypatch, capsys)["correct"]


def test_the_float8_control_is_not_correct():
    config, traffic = cell_pieces()
    ref = train.reference(config, traffic, SEED)
    low = train.reference(config, traffic, SEED, "float8")
    ok, checks = check.judge(check.train_numbers(low, ref),
                             traffic["limits"])
    assert not ok, checks


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged",
                                   "reward_altered", "split_ignored"])
def test_each_fault_the_driver_names_is_not_correct(fault):
    config, traffic = cell_pieces()
    faults = spec.driver_of(config).faults(config, traffic)
    assert set(faults) == {"half_batch", "state_unchanged",
                           "reward_altered", "split_ignored"}
    ref = train.reference(config, traffic, SEED)
    bad = train.reference(config, traffic, SEED, **faults[fault])
    ok, checks = check.judge(check.train_numbers(bad, ref),
                             traffic["limits"])
    assert not ok, (fault, checks)


def test_the_split_binds_in_most_on_path_intervals():
    """The cell's mechanism does most of the work: in the reference's first
    round, a link's thread share holds the network stage below what the
    flow's threads could move in most on-path (flow, link) intervals."""
    from ref import nets
    from ref import topology as ref_topology
    config, traffic = cell_pieces()
    s = SEED % (1 << 31)
    params, _ = nets.init_agent(s, config["agent"], 19)
    share = ref_topology.binding_share(
        params, nets.round_keys(s, 1)[0], ref_topology.world_of(config),
        config["agent"], traffic["n_envs"])
    assert 0.5 < float(share) <= 1.0
