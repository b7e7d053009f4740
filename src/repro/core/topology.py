"""Multi-link topology core: flows traverse PATHS of links.

Everything in :mod:`repro.core.fleet` contends for ONE bottleneck. The
paper's target regime — geographically dispersed transfers between Globus
endpoints — is a path of links (source site, one or more WAN segments,
destination site) whose binding constraint moves over time: the diurnal dip
hits the European segment hours before the US one, a failed link reroutes
traffic onto a narrower backup, cross traffic steals one segment while the
rest of the path idles. This module generalizes the fleet core to a
``LinkGraph`` of E links, each carrying its OWN ScheduleTable, plus a
``PathSpec`` routing each of the F flows over a subset of links
(piecewise-constant in time, so a failover can re-route flows mid-run):

    rate[f] = min over links e on f's path of  rate_on_link[f, e]

where each link splits its scheduled capacity across the flows ROUTED over
it exactly as the single-bottleneck fleet model does (thread-proportional
shares, floors guaranteed first), with one fidelity upgrade the ROADMAP
demanded: the per-link split is WORK-CONSERVING under rate caps. When a
capped flow cannot use its thread-proportional share, the unused capacity
is redistributed to the uncapped flows on that link (iterated water-fill
over the cap headroom — at most F rounds saturate every cap, so the loop
is a fixed F-round scan). The single-bottleneck model stranded that share
in the sim while the live token buckets redistributed it; here Σ flow
rates on a saturated link == the link's scheduled capacity whenever demand
suffices (property-pinned in tests/test_fleet_properties.py).

BIT-IDENTITY CONTRACT: E=1 with every flow routed over the one link and no
finite rate cap is the PR 5 fleet path at atol=0. Every term of the
redistribution is an exact float no-op when caps are infinite
(max(x - inf, 0) == 0, min(x, inf) == x, x + share*0.0 == x), the min over
a single-link axis is an identity slice, and the base allocation is the
same expression tree ``guaranteed + share * residual`` the fleet solve
compiles — so the topology solve REPLACES ``_fleet_substep_rates`` as the
general case without perturbing a single pinned golden.

The live twin is ``repro.transfer.MultiLink``: one StageThrottle pool per
link; an engine's stage worker acquires tokens from EVERY pool on its path
(all-or-refund, so a blocked downstream link never strands tokens already
drawn upstream), reproducing the min-over-path rate with real token
buckets. ``TopologyController`` appends the ``TOPOLOGY_OBS`` features —
bottleneck-link utilization, path length, my-share-on-bottleneck — from
engine observe() dicts exactly as ``topology_observe`` derives them
(parity-pinned in tests/test_topology.py).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.schedule import ScheduleTable
from repro.core.simulator import (SimParams, ObservationSpec, DEFAULT_OBS,
                                  TOPO_DIM)
from repro.core.fleet import (FlowSchedule, FlowObjective, FleetState,
                              always_on, active_at, default_objectives,
                              fleet_observe, _delivered_or_zeros,
                              _integrate_fleet_rates, _fleet_reward,
                              _window_flow_ids, _gather_compact,
                              _sparse_fleet_observe)

# The topology state is the fleet state: per-flow buffers/threads/
# throughputs, one shared sim clock, per-flow delivered counters. Only the
# WORLD around it (graph + paths instead of one table) changes.
TopologyState = FleetState


class LinkGraph(NamedTuple):
    """E links, each a piecewise-constant 3-stage ScheduleTable sharing one
    bin grid: ``tpt``/``bw`` are (E, T, 3), ``bin_seconds`` the shared bin
    width. All leaves are jnp arrays so a batch of graphs (leading env
    axis) vmaps like a batched ScheduleTable."""

    tpt: jnp.ndarray          # (E, T, 3) per-thread rate per link
    bw: jnp.ndarray           # (E, T, 3) aggregate cap per link
    bin_seconds: jnp.ndarray  # scalar

    @property
    def n_links(self) -> int:
        return self.tpt.shape[-3]


class PathSpec(NamedTuple):
    """Piecewise-constant routing: ``onpath[r, f, e]`` is 1.0 when flow f
    traverses link e during route bin r (bins of ``bin_seconds``, the last
    bin extends forever — the same clipped-gather lookup ScheduleTable
    uses). R=1 is static routing; a failover scenario uses R=2 with
    ``bin_seconds`` equal to the failure time."""

    onpath: jnp.ndarray       # (R, F, E) 0/1 routing matrix per route bin
    bin_seconds: jnp.ndarray  # scalar route-bin width

    @property
    def n_flows(self) -> int:
        return self.onpath.shape[-2]


class Topology(NamedTuple):
    """A (graph, paths) bundle — what ``train_ppo(topology=...)`` batches
    over (one pytree, so a leading env axis vmaps both together)."""

    graph: LinkGraph
    paths: PathSpec


def make_link_graph(tpt, bw, bin_seconds=1.0) -> LinkGraph:
    tpt = jnp.asarray(tpt, jnp.float32)
    bw = jnp.asarray(bw, jnp.float32)
    if tpt.ndim != 3 or tpt.shape[-1] != 3 or tpt.shape != bw.shape:
        raise ValueError(f"link graph wants matching (E, T, 3) arrays: "
                         f"{tpt.shape} vs {bw.shape}")
    if tpt.shape[0] < 1:
        raise ValueError("a link graph needs at least one link")
    return LinkGraph(tpt=tpt, bw=bw,
                     bin_seconds=jnp.asarray(bin_seconds, jnp.float32))


def single_link_graph(table: ScheduleTable) -> LinkGraph:
    """The E=1 embedding of a fleet-world ScheduleTable — the graph on
    which the topology solve is bit-identical to the fleet solve."""
    return LinkGraph(tpt=table.tpt[None], bw=table.bw[None],
                     bin_seconds=jnp.asarray(table.bin_seconds, jnp.float32))


def make_path_spec(onpath, bin_seconds=jnp.inf) -> PathSpec:
    """``onpath``: (F, E) for static routes or (R, F, E) for
    piecewise-constant routing with bins of ``bin_seconds`` (static routes
    keep the default inf bin: every time lands in bin 0)."""
    onpath = jnp.asarray(onpath, jnp.float32)
    if onpath.ndim == 2:
        onpath = onpath[None]
    if onpath.ndim != 3:
        raise ValueError(f"onpath must be (F, E) or (R, F, E), "
                         f"got {onpath.shape}")
    return PathSpec(onpath=onpath,
                    bin_seconds=jnp.asarray(bin_seconds, jnp.float32))


def all_links_path(n_flows: int, n_links: int) -> PathSpec:
    """Every flow traverses every link, forever — the series-path default
    (and, at E=1, the exact fleet world)."""
    return make_path_spec(jnp.ones((n_flows, n_links), jnp.float32))


def stack_link_graphs(graphs) -> LinkGraph:
    """Stack same-shape graphs into one batched LinkGraph (leading env
    axis) for vmapped training — the graph twin of ``stack_tables``."""
    graphs = list(graphs)
    shapes = {g.tpt.shape for g in graphs}
    if len(shapes) != 1:
        raise ValueError(f"cannot stack link graphs of shapes {shapes}")
    return LinkGraph(tpt=jnp.stack([g.tpt for g in graphs]),
                     bw=jnp.stack([g.bw for g in graphs]),
                     bin_seconds=jnp.stack([jnp.asarray(g.bin_seconds,
                                                        jnp.float32)
                                            for g in graphs]))


def stack_path_specs(paths) -> PathSpec:
    """Stack same-shape path specs into one batched PathSpec."""
    paths = list(paths)
    shapes = {p.onpath.shape for p in paths}
    if len(shapes) != 1:
        raise ValueError(f"cannot stack path specs of shapes {shapes}")
    return PathSpec(onpath=jnp.stack([p.onpath for p in paths]),
                    bin_seconds=jnp.stack([jnp.asarray(p.bin_seconds,
                                                       jnp.float32)
                                           for p in paths]))


def stack_topologies(topologies) -> Topology:
    topologies = list(topologies)
    return Topology(graph=stack_link_graphs(t.graph for t in topologies),
                    paths=stack_path_specs(t.paths for t in topologies))


def routes_at(paths: PathSpec, t):
    """(F, E) routing matrix at sim time ``t`` (an (S,) time vector returns
    (S, F, E)) — the route twin of ``active_at``."""
    R = paths.onpath.shape[0]
    idx = jnp.clip(jnp.floor(jnp.asarray(t, jnp.float32)
                             / paths.bin_seconds), 0, R - 1).astype(jnp.int32)
    return paths.onpath[idx]


def graph_peak_bw(graph: LinkGraph):
    """Max aggregate bandwidth anywhere in the graph — the observation /
    reward normalization reference (== ``peak_bw(table)`` at E=1)."""
    return jnp.maximum(jnp.max(graph.bw), 1e-9)


def link_peak_bw(graph: LinkGraph):
    """(E,) per-link peak bandwidth — the per-link utilization reference of
    ``topology_features``."""
    return jnp.maximum(jnp.max(graph.bw, axis=(-2, -1)), 1e-9)


def _sorted_water_fill(alloc, headroom, w, lam0):
    """Closed-form fixed point of the F-round spill loop, O(A log A) in the
    flow axis (axis 1 of the (S, F, E, 3) operands) instead of O(F) dense
    rounds: the loop converges to ``alloc_f = min(headroom_f, w_f * lam)``
    with ``lam`` the water level at which the redistributed pool is
    exhausted (or every cap saturated). Sorting the saturation breakpoints
    ``r_f = headroom_f / w_f`` and prefix-summing consumption yields
    ``lam`` directly.

    Bitwise contract: when no cap is finite the round-1 spill is exactly
    0.0, so ``delta`` multiplies out to +0.0 and
    ``min(alloc + w*0.0, inf) == alloc`` — the same exact no-op chain the
    unrolled loop rides, keeping every no-cap pin unchanged. With finite
    caps the result matches the loop's fixed point only up to resummation
    order (pinned at tolerance in tests/test_fleet_properties.py)."""
    recv = w > 0                                       # only weighted flows
    h = jnp.where(recv, headroom, 0.0)                 # ...receive spill
    pool = alloc.sum(axis=1)                           # (S, E, 3)
    spill0 = jnp.maximum(alloc - headroom, 0.0).sum(axis=1)
    r = jnp.where(recv, headroom / jnp.where(recv, w, 1.0), jnp.inf)
    order = jnp.argsort(r, axis=1)
    r_s = jnp.take_along_axis(r, order, axis=1)
    h_s = jnp.take_along_axis(h, order, axis=1)
    w_s = jnp.take_along_axis(jnp.where(recv, w, 0.0), order, axis=1)
    w_tot = w_s.sum(axis=1)                            # (S, E, 3)
    w_rem = w_tot[:, None] - jnp.cumsum(w_s, axis=1)   # unsaturated past i
    # water consumed when the level reaches breakpoint r_i (inf entries —
    # uncapped flows — are masked where their remaining weight is zero so
    # inf * 0 never produces a NaN)
    cons = (jnp.cumsum(h_s, axis=1)
            + jnp.where(w_rem > 0, r_s, 0.0) * w_rem)
    sat = cons < pool[:, None]                         # fully submerged
    h_sat = jnp.where(sat, h_s, 0.0).sum(axis=1)
    w_unsat = w_tot - jnp.where(sat, w_s, 0.0).sum(axis=1)
    lam = (pool - h_sat) / jnp.maximum(w_unsat, 1e-9)
    delta = jnp.where(spill0 > 0.0, jnp.maximum(lam - lam0, 0.0), 0.0)
    return jnp.minimum(alloc + w * delta[:, None], headroom)


def _topology_substep_rates(params: SimParams, graph: LinkGraph,
                            paths: PathSpec, threads, flows: FlowSchedule,
                            t0, substeps: int,
                            objectives: FlowObjective = None, *,
                            water_fill="rounds"):
    """(substeps, F, 3) per-flow rates over the link graph: each link
    splits its scheduled capacity across the flows routed over it (the
    fleet contention model, per link), each flow's rate is the min over
    the links on its path, and — the work-conserving upgrade — capacity a
    capped flow cannot use is redistributed to the uncapped flows on that
    link (at most F water-fill rounds saturate every cap).

    Off-path links never constrain a flow (masked to +inf before the min);
    a flow with an empty path moves nothing. E=1 / all-routed / no-caps is
    ``_fleet_substep_rates`` bit-for-bit: the redistribution is an exact
    float no-op when every cap is infinite, and the min over one link is
    an identity slice.

    ``water_fill`` selects the redistribution algorithm: "rounds" (the
    default and the bitwise reference) unrolls the F spill rounds;
    "sorted" computes the same fixed point in closed form via
    ``_sorted_water_fill`` — O(A log A), what the sparse compact-set path
    uses (identical when no cap is finite; tolerance-pinned otherwise)."""
    dt = params.duration / substeps
    T = graph.tpt.shape[-2]
    n_flows = threads.shape[0]
    ts = t0 + dt * jnp.arange(substeps, dtype=jnp.float32)
    idx = jnp.clip(jnp.floor(ts / graph.bin_seconds), 0, T - 1)
    idx = idx.astype(jnp.int32)
    tpt = jnp.swapaxes(graph.tpt[:, idx], 0, 1)        # (S, E, 3)
    bw = jnp.swapaxes(graph.bw[:, idx], 0, 1)          # (S, E, 3)
    act = active_at(flows, ts)                         # (S, F)
    onpath = routes_at(paths, ts)                      # (S, F, E)
    # effective threads of flow f ON link e (0 off-path / inactive)
    eff = (threads[None, :, None, :] * act[:, :, None, None]
           * onpath[..., None])                        # (S, F, E, 3)
    total = jnp.maximum(eff.sum(axis=1), 1e-9)         # (S, E, 3)
    share = eff / total[:, None]                       # (S, F, E, 3)
    if objectives is None:
        link_rate = jnp.minimum(eff * tpt[:, None], share * bw[:, None])
    else:
        cap = objectives.rate_cap[None, :, None, None]
        demand = jnp.minimum(eff * tpt[:, None], cap)  # (S, F, E, 3)
        guaranteed = jnp.minimum(
            objectives.rate_floor[None, :, None, None], demand)
        g_tot = guaranteed.sum(axis=1)                 # (S, E, 3)
        # oversubscribed floors shrink proportionally; sum stays <= bw
        guaranteed = guaranteed * jnp.minimum(
            1.0, bw / jnp.maximum(g_tot, 1e-9))[:, None]
        residual = jnp.maximum(bw - guaranteed.sum(axis=1), 0.0)
        alloc = share * residual[:, None]              # (S, F, E, 3)
        # Water-fill the cap headroom: capacity allocated past a flow's cap
        # spills to the flows still below theirs, thread-proportionally.
        # Every round saturates at least one more cap while any spill
        # remains, so F rounds reach the fixed point; with all caps at inf
        # every term below is an exact float no-op (headroom = inf).
        headroom = cap - guaranteed                    # inf when uncapped
        if water_fill == "sorted":
            alloc = _sorted_water_fill(alloc, headroom, eff,
                                       residual / total)
        else:
            for _ in range(n_flows):
                spill = jnp.maximum(alloc - headroom, 0.0).sum(axis=1)
                alloc = jnp.minimum(alloc, headroom)
                w = eff * (alloc < headroom)
                w_tot = jnp.maximum(w.sum(axis=1), 1e-9)
                alloc = alloc + (w / w_tot[:, None]) * spill[:, None]
            alloc = jnp.minimum(alloc, headroom)
        link_rate = jnp.minimum(demand, guaranteed + alloc)
    # a flow's end-to-end rate: min over ITS links; off-path links never
    # constrain, an empty path moves nothing. The trailing act mask is the
    # all-inactive guard (a bitwise no-op — see _fleet_substep_rates).
    constraining = jnp.where(onpath[..., None] > 0, link_rate, jnp.inf)
    rate = jnp.min(constraining, axis=2)               # (S, F, 3)
    has_path = onpath.sum(axis=2) > 0                  # (S, F)
    return jnp.where(has_path[..., None], rate, 0.0) * act[..., None]


def _solve_topology_rates(params: SimParams, graph: LinkGraph,
                          paths: PathSpec, threads, flows: FlowSchedule,
                          t0, substeps: int, objectives, backend,
                          water_fill="rounds"):
    """(S, F, 3) topology rates with the backend knob: "jnp" is the dense
    reference solve; "pallas" fuses the whole per-substep solve — caps,
    scaled floors, proportional residual split, the F-round water-fill,
    and the min-over-path-links — into the repro.kernels.contention kernel
    (interpret mode on the CPU; pinned vs the reference in tests)."""
    if backend == "pallas":
        from repro.kernels.contention.ops import contention_rates
        dt = params.duration / substeps
        T = graph.tpt.shape[-2]
        ts = t0 + dt * jnp.arange(substeps, dtype=jnp.float32)
        idx = jnp.clip(jnp.floor(ts / graph.bin_seconds), 0, T - 1)
        idx = idx.astype(jnp.int32)
        tpt = jnp.swapaxes(graph.tpt[:, idx], 0, 1)    # (S, E, 3)
        bw = jnp.swapaxes(graph.bw[:, idx], 0, 1)      # (S, E, 3)
        act = active_at(flows, ts)                     # (S, F)
        onpath = routes_at(paths, ts)                  # (S, F, E)
        floor = objectives.rate_floor if objectives is not None else None
        cap = objectives.rate_cap if objectives is not None else None
        return contention_rates(threads, act, onpath, tpt, bw,
                                floor=floor, cap=cap,
                                rounds=threads.shape[0])
    return _topology_substep_rates(params, graph, paths, threads, flows,
                                   t0, substeps, objectives,
                                   water_fill=water_fill)


def _sparse_topology_interval(params: SimParams, graph, paths, buffers,
                              threads, t0, flows: FlowSchedule, substeps,
                              backend, objectives, max_active: int,
                              return_compact=False):
    """Compact-active-set fast path of ``topology_interval``: the fleet
    gather plus a column gather of the routing matrix, and the sort-based
    water-fill instead of the F-round spill loop (O(A log A) in the
    compact size). No-cap fleets match the dense solve to float32 ulp
    noise (the same reassociation caveat as ``_sparse_fleet_interval``);
    capped fleets match the spill loop's fixed point at 1e-5 (the sorted
    fill reaches the same limit in closed form).

    ``return_compact`` additionally hands back the interval's gather so
    ``topology_step`` scores the reward on the same compact set — see
    ``_sparse_fleet_interval``."""
    F = flows.n_flows
    idx = _window_flow_ids(flows, t0, params.duration, max_active)
    c_threads, c_flows, c_objs = _gather_compact(idx, F, threads, flows,
                                                 objectives)
    safe = jnp.minimum(idx, F - 1)
    valid = idx < F
    c_paths = PathSpec(
        onpath=jnp.where(valid[None, :, None], paths.onpath[:, safe], 0.0),
        bin_seconds=paths.bin_seconds)
    c_bufs = jnp.where(valid[:, None], buffers[safe], 0.0)
    with jax.named_scope("topology.solve"):
        rates = _solve_topology_rates(params, graph, c_paths, c_threads,
                                      c_flows, t0, substeps, c_objs, backend,
                                      water_fill="sorted")
    with jax.named_scope("topology.integrate"):
        c_bufs, c_tps = _integrate_fleet_rates(params, c_bufs, rates,
                                               backend)
    new_buffers = buffers.at[idx].set(c_bufs, mode="drop")
    tps = jnp.zeros_like(threads).at[idx].set(c_tps, mode="drop")
    if return_compact:
        return (new_buffers, tps, idx, valid, c_tps, c_threads, c_flows,
                c_objs)
    return new_buffers, tps


def topology_interval(params: SimParams, buffers, threads, t0=0.0, *,
                      graph: LinkGraph, paths: PathSpec,
                      flows: FlowSchedule, substeps=50, backend="jnp",
                      objectives: FlowObjective = None,
                      max_active: int = None):
    """Simulate ``duration`` seconds of F flows over the link graph —
    the topology twin of ``fleet_interval`` (same buffer dynamics, same
    backends; only the rate solve differs). ``max_active``: optional
    static bound on per-interval concurrency — gathers the compact active
    set and runs the sort-based water-fill on it (see ``fleet_interval``
    for the contract)."""
    t0 = jnp.asarray(t0, jnp.float32)
    if max_active is not None and max_active < flows.n_flows:
        return _sparse_topology_interval(params, graph, paths, buffers,
                                         threads, t0, flows, substeps,
                                         backend, objectives, max_active)
    with jax.named_scope("topology.solve"):
        rates = _solve_topology_rates(params, graph, paths, threads, flows,
                                      t0, substeps, objectives, backend)
    with jax.named_scope("topology.integrate"):
        return _integrate_fleet_rates(params, buffers, rates, backend)


def pad_path_spec(paths: PathSpec, n_to: int) -> PathSpec:
    """Pad the routing matrix to ``n_to`` flows with all-zero rows (no
    path): a pathless flow moves nothing and scores zero utility, so
    padding is reward-exact — the routing twin of
    ``repro.core.fleet.pad_flow_schedule``. Batched specs (leading env
    axes) pad the same way."""
    pad = n_to - paths.n_flows
    if pad < 0:
        raise ValueError(f"cannot pad {paths.n_flows} flows down to {n_to}")
    if pad == 0:
        return paths
    shape = paths.onpath.shape[:-2] + (pad,) + paths.onpath.shape[-1:]
    return PathSpec(
        onpath=jnp.concatenate([paths.onpath,
                                jnp.zeros(shape, jnp.float32)], axis=-2),
        bin_seconds=paths.bin_seconds)


def topology_features(onpath, net_tps, active, link_bw_ref):
    """(F, TOPO_DIM) topology observation block — the ONE definition both
    ``topology_observe`` (sim) and ``TopologyController`` (live) emit:

      [0] bottleneck-link utilization — aggregate network throughput over
          capacity on the most-loaded link of MY path (0 for empty paths)
      [1] path length / E — how much of the graph I traverse
      [2] my share of the aggregate on that bottleneck link

    ``onpath``: (F, E) routing at the current time; ``net_tps``: (F,)
    network-stage throughputs; ``active``: (F,) 0/1; ``link_bw_ref``: (E,)
    per-link bandwidth reference (sim: per-link schedule peak; live: the
    driver-provisioned link capacities in engine units)."""
    onpath = jnp.asarray(onpath, jnp.float32)
    net = (jnp.asarray(net_tps, jnp.float32)
           * jnp.asarray(active, jnp.float32))         # (F,)
    agg = (onpath * net[:, None]).sum(axis=0)          # (E,) load per link
    util = agg / jnp.maximum(jnp.asarray(link_bw_ref, jnp.float32), 1e-9)
    on_util = jnp.where(onpath > 0, util[None, :], -jnp.inf)   # (F, E)
    bneck = jnp.argmax(on_util, axis=1)                # (F,)
    has_path = onpath.sum(axis=1) > 0
    b_util = jnp.where(has_path, jnp.take(util, bneck), 0.0)
    my_share = jnp.where(
        has_path, net / jnp.maximum(jnp.take(agg, bneck), 1e-9), 0.0)
    path_len = onpath.sum(axis=1) / onpath.shape[1]
    return jnp.stack([b_util, path_len, my_share], axis=-1)


def _sparse_topology_observe(params: SimParams, state: TopologyState, *,
                             flows, graph, paths, spec, objectives,
                             max_active: int):
    """Compact-active-set fast path of ``topology_observe``: the sparse
    fleet-observe gather plus a row gather of the routing matrix feeding
    ``topology_features`` on the compact set (the per-link load sums drop
    only exact +0.0 terms — inactive flows contribute ``net * 0``).
    Ungathered rows scatter back as EXACTLY zero; gathered rows match the
    dense path to float32 ulp. Same contract as ``_sparse_fleet_observe``."""
    F = state.threads.shape[0]
    base = _sparse_fleet_observe(params, state, flows=flows, spec=spec,
                                 objectives=objectives,
                                 bw_ref=graph_peak_bw(graph),
                                 max_active=max_active)
    if not getattr(spec, "topology", False):
        return base
    idx = _window_flow_ids(flows, state.t, params.duration, max_active)
    safe = jnp.minimum(idx, F - 1)
    valid = idx < F
    c_flows = FlowSchedule(
        t_start=jnp.where(valid, flows.t_start[safe], jnp.inf),
        t_end=jnp.where(valid, flows.t_end[safe], jnp.inf),
        down_start=(None if flows.down_start is None else
                    jnp.where(valid, flows.down_start[safe], jnp.inf)),
        down_end=(None if flows.down_end is None else
                  jnp.where(valid, flows.down_end[safe], jnp.inf)))
    onpath = routes_at(paths, state.t)                 # (F, E)
    c_onpath = jnp.where(valid[:, None], onpath[safe], 0.0)
    c_net = jnp.where(valid, state.throughputs[safe, 1], 0.0)
    topo = topology_features(c_onpath, c_net, active_at(c_flows, state.t),
                             link_peak_bw(graph))
    topo_full = jnp.zeros((F, topo.shape[-1]), topo.dtype).at[idx].set(
        topo, mode="drop")
    return jnp.concatenate([base, topo_full], axis=-1)


def topology_observe(params: SimParams, state: TopologyState, *,
                     flows: FlowSchedule, graph: LinkGraph, paths: PathSpec,
                     spec: ObservationSpec = DEFAULT_OBS,
                     objectives: FlowObjective = None,
                     max_active: int = None):
    """(F, spec.frame_dim) observation matrix: the fleet observation
    normalized by the GRAPH's peak bandwidth, optionally extended
    (spec.topology) with the ``topology_features`` block. At E=1 the
    graph peak equals the table peak, so a topology-blind spec reproduces
    ``fleet_observe`` bit-for-bit. ``max_active``: optional static
    concurrency bound — the feature program runs on the compact gathered
    set only (see ``fleet_observe`` for the contract)."""
    with jax.named_scope("topology.observe"):
        if max_active is not None and max_active < state.threads.shape[0]:
            return _sparse_topology_observe(params, state, flows=flows,
                                            graph=graph, paths=paths,
                                            spec=spec, objectives=objectives,
                                            max_active=max_active)
        bw_ref = graph_peak_bw(graph)
        base = fleet_observe(params, state, flows=flows, spec=spec,
                             objectives=objectives, bw_ref=bw_ref)
        if not getattr(spec, "topology", False):
            return base
        onpath = routes_at(paths, state.t)             # (F, E)
        act = active_at(flows, state.t)
        topo = topology_features(onpath, state.throughputs[:, 1], act,
                                 link_peak_bw(graph))
        return jnp.concatenate([base, topo], axis=-1)


@partial(jax.jit, static_argnames=("n_flows", "substeps", "spec", "backend",
                                   "max_active"))
def topology_reset(params: SimParams, key, n_flows: int, t0=0.0, *,
                   graph: LinkGraph, paths: PathSpec,
                   flows: FlowSchedule = None, substeps=50,
                   spec: ObservationSpec = DEFAULT_OBS, backend="jnp",
                   objectives: FlowObjective = None, max_active: int = None):
    """The topology twin of ``fleet_reset``: same key stream (the (F, 3)
    thread draw), empty buffers, one warm-up interval over the graph."""
    if flows is None:
        flows = always_on(n_flows)
    threads = jax.random.randint(key, (n_flows, 3), 1, 16).astype(jnp.float32)
    buffers = jnp.zeros((n_flows, 2), jnp.float32)
    t0 = jnp.asarray(t0, jnp.float32)
    buffers, tps = topology_interval(params, buffers, threads, t0,
                                     graph=graph, paths=paths, flows=flows,
                                     substeps=substeps, backend=backend,
                                     objectives=objectives,
                                     max_active=max_active)
    return TopologyState(buffers=buffers, threads=threads, throughputs=tps,
                         t=t0 + params.duration, prev_throughputs=tps,
                         delivered=jnp.zeros((n_flows,), jnp.float32))


@partial(jax.jit, static_argnames=("substeps", "spec", "backend",
                                   "max_active"))
def topology_step(params: SimParams, state: TopologyState, actions, *,
                  graph: LinkGraph, paths: PathSpec,
                  flows: FlowSchedule = None, substeps=50,
                  spec: ObservationSpec = DEFAULT_OBS, backend="jnp",
                  fairness_coef=0.0, objectives: FlowObjective = None,
                  deadline_coef=1.0, max_active: int = None):
    """actions (F, 3) -> round -> clamp [1, n_max]; one ``duration``-second
    interval over the graph. Returns (state', obs (F, frame_dim), reward).
    The reward is the shared fleet objective (``_fleet_reward`` — ONE
    definition), normalized by the graph peak. The rate solve, the buffer
    integration, the observation and the reward run under the named
    scopes ``topology.solve``, ``topology.integrate``, ``topology.observe``
    and ``topology.reward`` (``topology_interval`` and ``topology_observe``
    open the first three): op metadata only, the compiled numbers are the
    same."""
    if flows is None:
        flows = always_on(state.threads.shape[0])
    threads = jnp.clip(jnp.round(actions), 1.0, params.n_max)
    bw_ref = graph_peak_bw(graph)
    t_mid = state.t + 0.5 * params.duration
    sparse = max_active is not None and max_active < state.threads.shape[0]
    if sparse:
        # one gather serves the solve AND the reward — see fleet_step
        (buffers, tps, idx, valid, c_tps, c_threads, c_flows,
         c_objs) = _sparse_topology_interval(
            params, graph, paths, state.buffers, threads, state.t, flows,
            substeps, backend, objectives, max_active, return_compact=True)
    else:
        buffers, tps = topology_interval(
            params, state.buffers, threads, state.t, graph=graph,
            paths=paths, flows=flows, substeps=substeps, backend=backend,
            objectives=objectives, max_active=max_active)
    delivered0 = _delivered_or_zeros(state)
    new_state = TopologyState(
        buffers=buffers, threads=threads, throughputs=tps,
        t=state.t + params.duration, prev_throughputs=state.throughputs,
        delivered=delivered0 + tps[:, 2] * params.duration)
    with jax.named_scope("topology.reward"):
        if sparse:
            c_objs = (default_objectives(max_active) if c_objs is None
                      else c_objs)
            c_delivered0 = jnp.where(
                valid, delivered0[jnp.minimum(idx, delivered0.shape[0] - 1)],
                0.0)
            reward = _fleet_reward(params, c_tps, c_threads,
                                   active_at(c_flows, t_mid), c_objs,
                                   c_delivered0, state.t, bw_ref,
                                   fairness_coef, deadline_coef)
        else:
            objs = (default_objectives(state.threads.shape[0])
                    if objectives is None else objectives)
            reward = _fleet_reward(params, tps, threads,
                                   active_at(flows, t_mid), objs, delivered0,
                                   state.t, bw_ref, fairness_coef,
                                   deadline_coef)
    obs = topology_observe(params, new_state, flows=flows, graph=graph,
                           paths=paths, spec=spec, objectives=objectives,
                           max_active=max_active)
    return new_state, obs, reward


def topology_achievable(params: SimParams, graph: LinkGraph,
                        paths: PathSpec, flows: FlowSchedule, t,
                        objectives: FlowObjective = None):
    """Best aggregate end-to-end rate the active fleet could sustain over
    the graph at sim time ``t``: run the contention solve at full
    concurrency (every flow at n_max on every stage) and sum the per-flow
    end-to-end bottlenecks — the topology generalization of
    ``fleet_achievable`` (0 when no flow is active)."""
    n_flows = paths.onpath.shape[-2]
    threads = jnp.full((n_flows, 3), params.n_max, jnp.float32)
    rates = _topology_substep_rates(params, graph, paths, threads, flows,
                                    jnp.asarray(t, jnp.float32), 1,
                                    objectives)                # (1, F, 3)
    return jnp.min(rates[0], axis=-1).sum()
