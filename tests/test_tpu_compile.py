"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes that break the (8, 128) tiling rule, shape casts Mosaic
refuses, and kernels that outgrow VMEM. Nothing runs; the values are
checked on the chip by ``chip_smoke.py``.

The topology is described inside a module fixture: only one process at a
time may load the TPU library, so describing it at import would make every
test worker but one fail to collect this file.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compiled_kernel_in
from repro.kernels.contention.kernel import contention_rates_pallas
from repro.kernels.contention.ops import contention_rates
from repro.kernels.sim_step.kernel import sim_interval_pallas, sim_step_pallas
from repro.kernels.sim_step.ops import sim_interval_batch

# the widths of chip_smoke.py: the single-flow trainer's env batch, and the
# topology fleet's active-set bound A over E=8 links (F=4096 flows whose
# Poisson windows leave at most A active in any one step)
ENVS, SUBSTEPS = 4096, 50
ACTIVE, LINKS = 256, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]


def _compiled_text(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()


@pytest.mark.parametrize("name", ["sim_step", "sim_step_sched"])
def test_sim_step_kernels_compile(one_chip, name):
    if name == "sim_step":
        args = _shapes(one_chip, (ENVS, 2), (ENVS, 3), (ENVS, 2))
        text = _compiled_text(sim_step_pallas, *args, substeps=SUBSTEPS,
                              interpret=False)
    else:
        args = _shapes(one_chip, (ENVS, 2), (ENVS, SUBSTEPS, 3), (ENVS, 2))
        text = _compiled_text(sim_interval_pallas, *args, interpret=False)
    assert compiled_kernel_in(text, name)


@pytest.mark.parametrize("n_links,with_objectives,rounds", [
    (LINKS, True, ACTIVE),   # topology solve: caps, floors, water-fill
    (LINKS, False, 0),       # topology solve, objective-free
    (1, True, 0),            # single-bottleneck fleet (E=1 embedding)
])
def test_contention_kernel_compiles(one_chip, n_links, with_objectives,
                                    rounds):
    F, E, S = ACTIVE, n_links, SUBSTEPS
    args = _shapes(one_chip, (F, 3), (S, F), (S, F, E), (S, E, 3),
                   (S, E, 3), (F, 3), (F, 3))
    text = _compiled_text(contention_rates_pallas, *args,
                          with_objectives=with_objectives, rounds=rounds,
                          interpret=False)
    assert compiled_kernel_in(text, "contention_solve")


def test_sim_interval_batch_compiles(one_chip):
    """The jitted wrapper the ``backend="pallas"`` simulator calls, compiled
    rather than interpreted (its values are compared on the chip)."""
    bufs, rates, cap = _shapes(one_chip, (8, 2), (8, SUBSTEPS, 3), (8, 2))
    lowered = sim_interval_batch.lower(bufs, rates, cap, interpret=False)
    compiled = lowered.compile()
    out_bufs, moved = compiled.out_info
    assert out_bufs.shape == (8, 2) and moved.shape == (8, 3)
    assert compiled_kernel_in(compiled.as_text(), "sim_step_sched")


def test_contention_rates_compiles(one_chip):
    """The jitted contention wrapper at a small odd fleet (F=5, E=2): block
    shapes that span whole dims must pass the tiling rule at any width."""
    F, E, S = 5, 2, 4
    threads, act, onpath, tpt, bw = _shapes(one_chip, (F, 3), (S, F),
                                            (S, F, E), (S, E, 3), (S, E, 3))
    floor, cap = _shapes(one_chip, (F,), (F,))
    compiled = contention_rates.lower(threads, act, onpath, tpt, bw, floor,
                                      cap, rounds=5,
                                      interpret=False).compile()
    assert compiled.out_info.shape == (S, F, 3)
    assert compiled_kernel_in(compiled.as_text(), "contention_solve")
    assert np.dtype(compiled.out_info.dtype) == np.float32
