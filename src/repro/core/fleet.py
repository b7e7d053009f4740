"""Multi-flow fleet core: F concurrent transfers sharing one bottleneck.

Everything below `repro.core.simulator` models ONE transfer. Production
endpoints serve MANY: each user's transfer ("flow") runs its own 3-stage
pipeline with its own staging buffers and thread pools, but the aggregate
stage bandwidths — the disk arrays and the network link of the shared
ScheduleTable — are one pool. This module generalizes the schedule-native
env core to a fleet of F flows with a thread-proportional contention model:

    eff[f]   = threads[f] * active[f]                 (per stage)
    share[f] = eff[f] / sum_g eff[g]
    rate[f]  = min(eff[f] * TPT, share[f] * B)

i.e. each flow's aggregate stage rate is capped by ITS share of the
scheduled capacity, where shares follow thread counts — exactly how N
greedy TCP-stream pools divide a bottleneck, and exactly what the live
``SharedLink`` (one StageThrottle pool under many TransferEngines)
reproduces with real token buckets: more threads acquire more tokens.

F = 1 is bit-identical to the single-flow path (share = n/n = 1.0 exactly,
so ``min(n*TPT, 1.0*B)`` is the same float program as ``min(n*TPT, B)``)
— pinned at atol=0 in tests/test_fleet.py next to the PR 2 goldens.

Flows join and leave mid-episode through a ``FlowSchedule`` — per-flow
[t_start, t_end) activity windows generated by the arrival families in
``repro.scenarios.families`` (poisson_arrivals, staggered_start,
flash_crowd). Inactive flows contribute zero effective threads, move zero
bytes, and are masked out of the fairness reward.

The reward generalizes the paper's utility to the fleet objective the
training ISSUE asks for:

    R = sum_f w_f * U(t_f, n_f)
        - deadline_coef * sum_f w_f * miss_penalty_f
        + fairness_coef * Jain(goodput_f / w_f over active flows)

The utility sum is the aggregate goodput term (with the paper's k^-n
resource penalty built in); Jain's index pushes the shared policy toward
splitting the link evenly instead of starving late arrivals.

HETEROGENEOUS OBJECTIVES (``FlowObjective``): real fleets are not uniform —
a checkpoint restore racing a deadline shares the link with bulk mirrors
that only care about eventual completion. Every flow can carry its own
objective: a priority WEIGHT (gold/silver/bronze tiers — scales its utility
and its claim in the weighted Jain term), an optional DEADLINE (finish
``demand`` Gbit by ``deadline`` sim-seconds; missing it costs a smooth
softplus hinge on the rate still needed, see repro.core.utility), and
optional RATE FLOOR / CAP that the contention model itself enforces: each
active flow is first guaranteed min(floor, what it can use) of the stage
capacity (floors are scaled down proportionally if oversubscribed), the
residual splits thread-proportionally as before, and a cap clamps the
flow's demand outright. The live ``SharedLink`` honors the same floors and
caps with per-engine token buckets layered on the shared pool.

The DEFAULT objective (weight = 1, no deadline, floor 0, cap inf) is
bit-identical to the objective-free PR 4 fleet path: 1.0 * u == u,
min(x, inf) == x, x + 0.0 == x, and the masked penalty is exactly 0.0 —
pinned at atol=0 in tests/test_objectives.py next to the PR 4 pins.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.schedule import ScheduleTable, peak_bw, schedule_at
from repro.core.simulator import (SimParams, ObservationSpec, DEFAULT_OBS,
                                  _table_or_params, _scan_substeps)
from repro.core.utility import (utility, flow_utility, needed_rate,
                                needed_rate_np, deadline_penalty)


class FlowSchedule(NamedTuple):
    """Per-flow activity windows: flow ``f`` is active on
    ``[t_start[f], t_end[f])`` of the sim clock. All leaves are jnp arrays so
    a batch of schedules (leading env axis) vmaps like any other pytree.

    ``down_start``/``down_end`` are the OPTIONAL liveness-fault fields: flow
    ``f`` is forced inactive on ``[down_start[f], down_end[f])`` — one
    kill -> restart outage carved out of its activity window (compiled from
    a FaultSpec by ``repro.scenarios.faults``; a kill with no restart is a
    ``t_end`` truncation instead). None — the default — is the fault-free
    world and leaves the PR 7 trace literally unchanged; an all-inf down
    window is the same program with two extra exact no-op compares
    ((t >= inf) & (t < inf) is False everywhere)."""

    t_start: jnp.ndarray  # (F,)
    t_end: jnp.ndarray    # (F,)
    down_start: jnp.ndarray = None  # (F,) outage start, or None
    down_end: jnp.ndarray = None    # (F,) outage end (restart), or None

    @property
    def n_flows(self) -> int:
        return self.t_start.shape[-1]


def make_flow_schedule(t_start, t_end, down_start=None,
                       down_end=None) -> FlowSchedule:
    t_start = jnp.asarray(t_start, jnp.float32)
    t_end = jnp.asarray(t_end, jnp.float32)
    if t_start.shape != t_end.shape or t_start.ndim != 1:
        raise ValueError(f"flow schedule must be two (F,) arrays: "
                         f"{t_start.shape} vs {t_end.shape}")
    if (down_start is None) != (down_end is None):
        raise ValueError("pass both down_start and down_end, or neither")
    if down_start is not None:
        down_start = jnp.asarray(down_start, jnp.float32)
        down_end = jnp.asarray(down_end, jnp.float32)
        if down_start.shape != t_start.shape or down_end.shape != t_start.shape:
            raise ValueError(f"down windows must match the (F,) schedule: "
                             f"{down_start.shape}/{down_end.shape} vs "
                             f"{t_start.shape}")
    return FlowSchedule(t_start=t_start, t_end=t_end,
                        down_start=down_start, down_end=down_end)


def always_on(n_flows: int) -> FlowSchedule:
    """All F flows active for the whole episode — the degenerate arrival
    schedule (and the F=1 single-flow world)."""
    return FlowSchedule(t_start=jnp.zeros((n_flows,), jnp.float32),
                        t_end=jnp.full((n_flows,), jnp.inf, jnp.float32))


def stack_flow_schedules(schedules) -> FlowSchedule:
    """Stack same-F schedules into one batched FlowSchedule (leading env
    axis) for vmapped training. Down windows stack too; a mix of faulted
    and fault-free schedules materializes the missing windows as all-inf
    (exact no-ops), all-None stays None (the fault-free trace)."""
    schedules = list(schedules)
    sizes = {s.t_start.shape[-1] for s in schedules}
    if len(sizes) != 1:
        raise ValueError(f"cannot stack fleets of different sizes {sizes}")
    if all(s.down_start is None for s in schedules):
        down_start = down_end = None
    else:
        def _inf_like(s):
            return jnp.full_like(s.t_start, jnp.inf)
        down_start = jnp.stack([s.down_start if s.down_start is not None
                                else _inf_like(s) for s in schedules])
        down_end = jnp.stack([s.down_end if s.down_end is not None
                              else _inf_like(s) for s in schedules])
    return FlowSchedule(
        t_start=jnp.stack([s.t_start for s in schedules]),
        t_end=jnp.stack([s.t_end for s in schedules]),
        down_start=down_start, down_end=down_end)


def active_at(flows: FlowSchedule, t):
    """(F,) float mask of flows active at sim time ``t`` (scalar or (S,) —
    an (S,) time vector returns (S, F)). A flow inside its down window
    (kill -> restart outage) is inactive; schedules without down windows
    (the default) run the exact PR 7 program."""
    t = jnp.asarray(t, jnp.float32)[..., None]
    act = (t >= flows.t_start) & (t < flows.t_end)
    if flows.down_start is not None:
        act &= ~((t >= flows.down_start) & (t < flows.down_end))
    return act.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fleet scale-out: pow2 buckets, padding, and the compact active-set gather
# ---------------------------------------------------------------------------


def flow_bucket(n: int) -> int:
    """Next power of two >= n (>= 1) — the F/A bucket grid that keeps
    resampled fleet sizes and active-set bounds on a handful of XLA shapes
    instead of recompiling per flow count."""
    return 1 << max(int(n) - 1, 0).bit_length()


def max_concurrent_flows(flows: FlowSchedule, *, window: float = 0.0) -> int:
    """Host-side event sweep: the max number of flows whose [t_start, t_end)
    activity window intersects ANY ``window``-second interval — the tight
    bound to feed ``max_active`` (pass the step ``duration`` as the window
    so no single interval's gather can overflow). Numpy on concrete values,
    not traceable: call it once when sizing a fleet, outside jit. Batched
    schedules (leading env axes) return the max over the batch."""
    ts = np.asarray(flows.t_start, np.float64)
    te = np.asarray(flows.t_end, np.float64)
    if ts.ndim > 1:
        flat = ts.reshape(-1, ts.shape[-1])
        flat_e = te.reshape(-1, te.shape[-1])
        return max(max_concurrent_flows(FlowSchedule(s, e), window=window)
                   for s, e in zip(flat, flat_e))
    starts = np.sort(ts - window)
    ends = np.sort(te)
    live = best = j = 0
    for s in starts:
        while j < len(ends) and ends[j] <= s:
            live -= 1
            j += 1
        live += 1
        best = max(best, live)
    return best


def pad_flow_schedule(flows: FlowSchedule, n_to: int) -> FlowSchedule:
    """Pad to ``n_to`` flows with never-active windows (t_start = t_end =
    inf): a padded flow contributes zero effective threads, moves zero
    bytes, scores exactly zero utility (u = sum t/k^n with t = 0), and is
    masked out of the Jain term — so padding never perturbs the reward
    (property-pinned). Works on batched schedules (leading env axes)."""
    pad = n_to - flows.n_flows
    if pad < 0:
        raise ValueError(f"cannot pad {flows.n_flows} flows down to {n_to}")
    if pad == 0:
        return flows
    inf = jnp.full(flows.t_start.shape[:-1] + (pad,), jnp.inf, jnp.float32)
    if flows.down_start is None:
        down_start = down_end = None
    else:  # a padded flow never runs, so its down window is moot: inf/inf
        down_start = jnp.concatenate([flows.down_start, inf], -1)
        down_end = jnp.concatenate([flows.down_end, inf], -1)
    return FlowSchedule(t_start=jnp.concatenate([flows.t_start, inf], -1),
                        t_end=jnp.concatenate([flows.t_end, inf], -1),
                        down_start=down_start, down_end=down_end)


# per-field fill values for padded / invalid flow objectives: the default
# objective, which is an exact no-op through the whole solve
_OBJECTIVE_FILLS = {"weight": 1.0, "deadline": jnp.inf, "demand": jnp.inf,
                    "rate_floor": 0.0, "rate_cap": jnp.inf}


def pad_flow_objectives(objectives, n_to: int):
    """Objective twin of ``pad_flow_schedule``: pads with the default
    objective (weight 1, no deadline, floor 0, cap inf). None stays None
    (the objective-free trace)."""
    if objectives is None:
        return None
    pad = n_to - objectives.n_flows
    if pad < 0:
        raise ValueError(f"cannot pad {objectives.n_flows} flows to {n_to}")
    if pad == 0:
        return objectives

    def _pad(x, fill):
        tail = jnp.full(x.shape[:-1] + (pad,), fill, jnp.float32)
        return jnp.concatenate([x, tail], -1)

    return type(objectives)(**{
        f: _pad(getattr(objectives, f), _OBJECTIVE_FILLS[f])
        for f in objectives._fields})


def _window_flow_ids(flows: FlowSchedule, t0, duration, max_active: int):
    """Compact indices of the flows whose activity window intersects
    [t0, t0 + duration): (max_active,) int32, empty slots filled with F
    (out of range, so ``.at[idx].set(..., mode="drop")`` discards them and
    gathers clamp them onto a slot that is forced inactive).
    ``jnp.nonzero(..., size=)`` keeps ascending flow order, so compact
    reductions visit the active flows in the same order the dense solve
    sums them — the compact partial sums only skip exact +0.0 terms, which
    keeps the sparse solve bitwise equal to the dense one.

    ``max_active`` is a caller PROMISE: if more flows than that intersect
    the window, the overflow is silently treated as absent for the
    interval. Size it with ``max_concurrent_flows(flows, window=duration)``
    (rounded up with ``flow_bucket``)."""
    F = flows.n_flows
    hit = (flows.t_start < t0 + duration) & (flows.t_end > t0)
    (idx,) = jnp.nonzero(hit, size=max_active, fill_value=F)
    return idx.astype(jnp.int32)


def _gather_compact(idx, n_flows, threads, flows, objectives):
    """Gather the compact (A,) slice of a fleet. Invalid slots (idx == F,
    the overflow fill) become never-active flows with default objectives,
    so every rate/utility term they touch is exactly zero."""
    safe = jnp.minimum(idx, n_flows - 1)
    valid = idx < n_flows
    c_threads = jnp.where(valid[:, None], threads[safe], 1.0)
    c_flows = FlowSchedule(
        t_start=jnp.where(valid, flows.t_start[safe], jnp.inf),
        t_end=jnp.where(valid, flows.t_end[safe], jnp.inf),
        down_start=(None if flows.down_start is None else
                    jnp.where(valid, flows.down_start[safe], jnp.inf)),
        down_end=(None if flows.down_end is None else
                  jnp.where(valid, flows.down_end[safe], jnp.inf)))
    if objectives is None:
        c_objs = None
    else:
        c_objs = type(objectives)(**{
            f: jnp.where(valid, getattr(objectives, f)[safe],
                         _OBJECTIVE_FILLS[f])
            for f in objectives._fields})
    return c_threads, c_flows, c_objs


# ---------------------------------------------------------------------------
# Per-flow objectives: priority tiers, deadlines, rate floors/caps
# ---------------------------------------------------------------------------

PRIORITY_TIERS = {"gold": 4.0, "silver": 2.0, "bronze": 1.0}
WEIGHT_REF = PRIORITY_TIERS["gold"]   # observation normalization reference
SLACK_REF = 20.0     # seconds: tanh scale of the deadline-slack feature
URGENCY_CLIP = 4.0   # cap on the needed-rate/bw_ref urgency feature


class FlowObjective(NamedTuple):
    """What each flow is FOR. All leaves are (F,) jnp arrays so a batch of
    objectives (leading env axis) vmaps like FlowSchedule. Defaults (ones /
    inf / zeros) are the objective-free world, bit-for-bit.

    weight      priority weight (PRIORITY_TIERS: gold 4, silver 2, bronze 1)
                — scales the flow's utility and its claim in the weighted
                Jain term
    deadline    sim-seconds by which ``demand`` should be delivered
                (inf = no deadline)
    demand      Gbit the deadline refers to (inf = no deadline objective)
    rate_floor  guaranteed per-stage rate the contention model reserves for
                the flow while it is active (0 = none)
    rate_cap    hard per-stage rate ceiling (inf = none)
    """

    weight: jnp.ndarray      # (F,)
    deadline: jnp.ndarray    # (F,)
    demand: jnp.ndarray      # (F,)
    rate_floor: jnp.ndarray  # (F,)
    rate_cap: jnp.ndarray    # (F,)

    @property
    def n_flows(self) -> int:
        return self.weight.shape[-1]


def make_flow_objective(n_flows=None, *, weight=None, deadline=None,
                        demand=None, rate_floor=None, rate_cap=None,
                        tiers=None) -> FlowObjective:
    """Build a FlowObjective from any subset of fields; omitted fields take
    their objective-free defaults. ``tiers`` is a convenience spelling of
    ``weight``: a sequence of tier names ("gold"/"silver"/"bronze")."""
    if tiers is not None:
        if weight is not None:
            raise ValueError("pass either weight or tiers, not both")
        weight = [PRIORITY_TIERS[t] for t in tiers]
    for v in (weight, deadline, demand, rate_floor, rate_cap):
        if v is None or jnp.asarray(v).ndim == 0:
            continue  # scalars broadcast to any F
        n = jnp.asarray(v, jnp.float32).shape[-1]
        if n_flows is None:
            n_flows = n
        elif n_flows != n:
            raise ValueError(f"objective fields disagree on F: "
                             f"{n_flows} vs {n}")
    if n_flows is None:
        raise ValueError("empty objective: pass n_flows or at least one "
                         "non-scalar field")

    def _field(v, default):
        if v is None:
            v = default
        arr = jnp.asarray(v, jnp.float32)
        if arr.ndim == 0:
            return jnp.full((n_flows,), arr, jnp.float32)
        return arr

    return FlowObjective(weight=_field(weight, 1.0),
                         deadline=_field(deadline, jnp.inf),
                         demand=_field(demand, jnp.inf),
                         rate_floor=_field(rate_floor, 0.0),
                         rate_cap=_field(rate_cap, jnp.inf))


def default_objectives(n_flows: int) -> FlowObjective:
    """The objective-free fleet: weight 1, no deadline, no floor/cap —
    bit-identical to the PR 4 path through every consumer."""
    return make_flow_objective(n_flows)


def stack_flow_objectives(objectives) -> FlowObjective:
    """Stack same-F objectives into one batched FlowObjective (leading env
    axis) for vmapped training — the objective twin of
    ``stack_flow_schedules``."""
    objectives = list(objectives)
    sizes = {o.weight.shape[-1] for o in objectives}
    if len(sizes) != 1:
        raise ValueError(f"cannot stack fleets of different sizes {sizes}")
    return FlowObjective(*(jnp.stack([getattr(o, f) for o in objectives])
                           for f in FlowObjective._fields))


def objective_features(objectives: FlowObjective, t, delivered, *,
                       bw_ref, duration=1.0):
    """(F, OBJ_DIM) objective observation block — the ONE definition both
    ``fleet_observe`` (sim) and ``FleetController`` (live) emit:

      [0] weight / WEIGHT_REF            — who am I?
      [1] tanh((deadline - t)/SLACK_REF) — how much time is left? (1.0 when
                                           no deadline; negative once past)
      [2] clip(needed_rate/bw_ref)       — how fast must I go to make it?
                                           (0 when no deadline)
    """
    t = jnp.asarray(t, jnp.float32)
    slack = jnp.tanh((objectives.deadline - t) / SLACK_REF)
    need = needed_rate(objectives.demand, delivered, objectives.deadline, t,
                       min_horizon=duration)
    urgency = jnp.clip(need / bw_ref, 0.0, URGENCY_CLIP)
    return jnp.stack([objectives.weight / WEIGHT_REF, slack, urgency],
                     axis=-1)


def objective_features_np(objectives: FlowObjective, t, delivered, *,
                          bw_ref, duration=1.0):
    """NumPy twin of ``objective_features`` for the live controller's hot
    path: the same float32 program with no device dispatch per control
    interval. The two definitions agree to float32 ulp (np.tanh vs XLA's
    tanh can differ in the last bit) — equality-pinned at 1e-6 in
    tests/test_controller_vectorized.py."""
    t = np.float32(t)
    weight = np.asarray(objectives.weight, np.float32)
    deadline = np.asarray(objectives.deadline, np.float32)
    demand = np.asarray(objectives.demand, np.float32)
    slack = np.tanh((deadline - t) / np.float32(SLACK_REF))
    need = needed_rate_np(demand, delivered, deadline, t,
                          min_horizon=duration)
    urgency = np.clip(need / np.float32(bw_ref), np.float32(0.0),
                      np.float32(URGENCY_CLIP))
    return np.stack([weight / np.float32(WEIGHT_REF), slack, urgency],
                    axis=-1).astype(np.float32)


class FleetState(NamedTuple):
    """The flow-batched twin of EnvState: one leading F axis on every
    per-flow leaf, one shared sim clock. ``delivered`` tracks each flow's
    cumulative goodput (Gbit) since reset — what deadline objectives are
    scored against (None on states built before the objective layer:
    consumers treat it as zeros)."""

    buffers: jnp.ndarray      # (F, 2) per-flow sender/receiver occupancy
    threads: jnp.ndarray      # (F, 3) per-flow concurrency
    throughputs: jnp.ndarray  # (F, 3) per-flow last measured throughput
    t: jnp.ndarray = 0.0      # scalar, shared sim clock
    prev_throughputs: jnp.ndarray = None  # (F, 3)
    delivered: jnp.ndarray = None         # (F,) cumulative goodput, Gbit


def _delivered_or_zeros(state: FleetState):
    if state.delivered is not None:
        return state.delivered
    return jnp.zeros(state.threads.shape[:-1], jnp.float32)


def _fleet_substep_rates(params: SimParams, table: ScheduleTable, threads,
                         flows: FlowSchedule, t0, substeps: int,
                         objectives: FlowObjective = None):
    """(substeps, F, 3) per-flow aggregate rates under contention: the
    scheduled capacity of each stage is split across the flows ACTIVE in
    that sub-interval in proportion to their thread counts.

    With ``objectives``, per-flow rate floors and caps shape the split:
    each flow's usable demand is clamped to its cap, every active flow is
    first GUARANTEED min(floor, demand) — floors scaled down
    proportionally when oversubscribed so the stage never over-commits —
    and only the residual capacity splits thread-proportionally. The
    default objective (floor 0, cap inf) reproduces the plain split
    bit-for-bit: min(x, inf) == x, 0.0 + share*(B - 0.0) == share*B."""
    dt = params.duration / substeps
    T = table.tpt.shape[0]
    ts = t0 + dt * jnp.arange(substeps, dtype=jnp.float32)
    idx = jnp.clip(jnp.floor(ts / table.bin_seconds), 0, T - 1)
    idx = idx.astype(jnp.int32)
    tpt = table.tpt[idx]                       # (S, 3)
    bw = table.bw[idx]                         # (S, 3)
    act = active_at(flows, ts)                 # (S, F)
    eff = threads[None, :, :] * act[:, :, None]        # (S, F, 3)
    total = jnp.maximum(eff.sum(axis=1), 1e-9)         # (S, 3)
    share = eff / total[:, None, :]                    # (S, F, 3)
    # the trailing act mask is the all-inactive guard: eff == 0 already
    # forces every inactive rate to +0.0, but the epsilon floors (the
    # 1e-9 clamps) sit close enough to that invariant that the mask makes
    # "inactive moves nothing" PROVABLE on every path — and it is a
    # bitwise no-op (x * 1.0 is exact for active flows, inactive rates
    # are already +0.0)
    if objectives is None:
        return (jnp.minimum(eff * tpt[:, None, :], share * bw[:, None, :])
                * act[:, :, None])
    demand = jnp.minimum(eff * tpt[:, None, :],
                         objectives.rate_cap[None, :, None])   # (S, F, 3)
    guaranteed = jnp.minimum(objectives.rate_floor[None, :, None], demand)
    g_tot = guaranteed.sum(axis=1)                             # (S, 3)
    # oversubscribed floors shrink proportionally; sum stays <= bw
    guaranteed = guaranteed * jnp.minimum(
        1.0, bw / jnp.maximum(g_tot, 1e-9))[:, None, :]
    residual = jnp.maximum(bw - guaranteed.sum(axis=1), 0.0)   # (S, 3)
    return jnp.minimum(demand,
                       guaranteed + share * residual[:, None, :]) \
        * act[:, :, None]


def _integrate_fleet_rates(params: SimParams, buffers, rates, backend):
    """Push precomputed (substeps, F, 3) per-flow rates through the shared
    buffer dynamics — the integration half of ``fleet_interval``, shared
    with the topology solve (any rate model that produces per-flow substep
    rates plugs in here). Returns (buffers' (F, 2), tps (F, 3))."""
    substeps = rates.shape[0]
    dt = params.duration / substeps
    rates = jnp.swapaxes(rates, 0, 1)          # (F, S, 3)
    if backend == "jnp":
        buffers, moved = jax.vmap(
            lambda b, r: _scan_substeps(b, r, params.cap, dt))(buffers, rates)
    elif backend == "pallas":
        from repro.kernels.sim_step.ops import sim_interval_batch
        F = buffers.shape[0]
        buffers, moved = sim_interval_batch(
            buffers, rates * dt, jnp.broadcast_to(params.cap, (F, 2)))
    else:
        raise ValueError(f"unknown simulator backend {backend!r}; "
                         "expected 'jnp' or 'pallas'")
    return buffers, moved / params.duration


def _solve_fleet_rates(params: SimParams, table: ScheduleTable, threads,
                       flows: FlowSchedule, t0, substeps: int, objectives,
                       backend):
    """(S, F, 3) contention rates with the backend knob: "jnp" is the dense
    reference solve, "pallas" routes the SAME solve through the fused
    contention kernel (repro.kernels.contention — caps, scaled floors, and
    the proportional residual split in one VMEM-resident kernel;
    interpret mode on the CPU, pinned against the reference at 1e-5 in
    tests/test_fleet.py and tests/test_fleet_scaleout.py). ``rounds=0``:
    the single-bottleneck fleet model does not water-fill."""
    if backend == "pallas":
        from repro.kernels.contention.ops import contention_rates
        dt = params.duration / substeps
        T = table.tpt.shape[0]
        ts = t0 + dt * jnp.arange(substeps, dtype=jnp.float32)
        idx = jnp.clip(jnp.floor(ts / table.bin_seconds), 0, T - 1)
        idx = idx.astype(jnp.int32)
        act = active_at(flows, ts)                        # (S, F)
        onpath = jnp.ones(act.shape + (1,), jnp.float32)  # E=1 embedding
        floor = objectives.rate_floor if objectives is not None else None
        cap = objectives.rate_cap if objectives is not None else None
        return contention_rates(threads, act, onpath,
                                table.tpt[idx][:, None],  # (S, 1, 3)
                                table.bw[idx][:, None],
                                floor=floor, cap=cap, rounds=0)
    return _fleet_substep_rates(params, table, threads, flows, t0,
                                substeps, objectives)


def _sparse_fleet_interval(params: SimParams, table, buffers, threads, t0,
                           flows: FlowSchedule, substeps, backend,
                           objectives, max_active: int,
                           return_compact=False):
    """The compact-active-set fast path of ``fleet_interval``: gather the
    <= max_active flows whose window intersects this interval, contend and
    integrate only those, scatter back. Flows outside the window moved
    nothing — their buffers pass through untouched and their throughputs
    are EXACTLY zero, the same values the dense path computes for them.
    The gathered flows match the dense solve to float32 ulp noise
    (pinned at 1e-6): the gather is order-preserving, but dropping an
    inactive flow's zero term from the middle of a cross-flow reduction
    shifts XLA's SIMD lane grouping, so partial sums can differ in the
    last bit (tests/test_fleet_scaleout.py, tests/test_fleet_properties
    pin both claims).

    ``return_compact`` additionally hands back the interval's gather
    (idx, valid, c_tps, c_threads, c_flows, c_objs) so ``fleet_step`` can
    score the reward on the SAME compact set instead of re-paying dense F
    for the utility/Jain/deadline reductions."""
    F = flows.n_flows
    idx = _window_flow_ids(flows, t0, params.duration, max_active)
    c_threads, c_flows, c_objs = _gather_compact(idx, F, threads, flows,
                                                 objectives)
    valid = idx < F
    c_bufs = jnp.where(valid[:, None], buffers[jnp.minimum(idx, F - 1)], 0.0)
    rates = _solve_fleet_rates(params, table, c_threads, c_flows, t0,
                               substeps, c_objs, backend)
    c_bufs, c_tps = _integrate_fleet_rates(params, c_bufs, rates, backend)
    new_buffers = buffers.at[idx].set(c_bufs, mode="drop")
    tps = jnp.zeros_like(threads).at[idx].set(c_tps, mode="drop")
    if return_compact:
        return (new_buffers, tps, idx, valid, c_tps, c_threads, c_flows,
                c_objs)
    return new_buffers, tps


def fleet_interval(params: SimParams, buffers, threads, t0=0.0, *,
                   flows: FlowSchedule, table=None, substeps=50,
                   backend="jnp", objectives: FlowObjective = None,
                   max_active: int = None):
    """Simulate ``duration`` seconds of F contending flows. ``buffers``
    (F, 2), ``threads`` (F, 3); returns (buffers' (F, 2), tps (F, 3)).
    ``objectives``: optional per-flow rate floors/caps shaping the split
    (None = the plain thread-proportional contention model).
    ``max_active``: optional STATIC bound on how many flows can be active
    in any one interval — the solve gathers that compact set, contends it,
    and scatters back, so the per-interval cost scales with the bound
    instead of the fleet size (equal to the dense solve to float32 ulp
    noise, exactly zero for the ungathered flows; size the bound with
    ``max_concurrent_flows``/``flow_bucket``). None or >= F runs the
    dense reference solve."""
    tab = _table_or_params(params, table)
    t0 = jnp.asarray(t0, jnp.float32)
    if max_active is not None and max_active < flows.n_flows:
        return _sparse_fleet_interval(params, tab, buffers, threads, t0,
                                      flows, substeps, backend, objectives,
                                      max_active)
    rates = _solve_fleet_rates(params, tab, threads, flows, t0, substeps,
                               objectives, backend)
    return _integrate_fleet_rates(params, buffers, rates, backend)


def _sparse_fleet_observe(params: SimParams, state: FleetState, *, flows,
                          spec, objectives, bw_ref, max_active: int):
    """Compact-active-set fast path of ``fleet_observe``: gather the
    <= max_active flows whose window intersects [t, t + duration) — a
    superset of the flows active at the observe instant, within the same
    ``max_concurrent_flows(window=duration)`` promise the interval solve
    rides — build the dense feature program on the compact set, scatter
    the rows back. Ungathered rows are EXACTLY zero (a flow the policy
    cannot steer this interval carries no features); gathered rows match
    the dense path to float32 ulp (the aggregate-throughput sum drops
    exact +0.0 terms, which only reassociates the reduction). The active
    fraction stays normalized by the TRUE fleet size via ``_n_total``."""
    F = state.threads.shape[0]
    idx = _window_flow_ids(flows, state.t, params.duration, max_active)
    safe = jnp.minimum(idx, F - 1)
    valid = idx < F
    c_threads, c_flows, c_objs = _gather_compact(idx, F, state.threads,
                                                 flows, objectives)

    def _rows(x):
        return jnp.where(valid[:, None], x[safe], 0.0)

    c_state = FleetState(
        buffers=_rows(state.buffers),
        threads=c_threads,
        throughputs=_rows(state.throughputs),
        t=state.t,
        prev_throughputs=(None if state.prev_throughputs is None
                          else _rows(state.prev_throughputs)),
        delivered=(None if state.delivered is None
                   else jnp.where(valid, state.delivered[safe], 0.0)))
    obs_c = fleet_observe(params, c_state, flows=c_flows, spec=spec,
                          objectives=c_objs, bw_ref=bw_ref, _n_total=F)
    return jnp.zeros((F, obs_c.shape[-1]), obs_c.dtype).at[idx].set(
        obs_c, mode="drop")


def fleet_observe(params: SimParams, state: FleetState, *,
                  flows: FlowSchedule, table=None,
                  spec: ObservationSpec = DEFAULT_OBS,
                  objectives: FlowObjective = None, bw_ref=None,
                  max_active: int = None, _n_total: int = None):
    """(F, spec.frame_dim) observation matrix: row f is exactly what the
    single-flow ``observe`` would produce from flow f's slice of the state,
    optionally extended (spec.fleet) with the 3 cross-flow dims — active
    fraction, aggregate link utilization, my-share — that the live
    FleetController derives identically from engine observe() dicts, and
    (spec.objectives) with the 3 per-flow objective dims of
    ``objective_features`` (None = the default objective). ``bw_ref``
    overrides the normalization reference (``topology_observe`` passes the
    graph peak; None = this table's peak, the fleet default).

    ``max_active``: optional static bound on per-interval concurrency —
    the feature program then runs on the compact gathered set only
    (gathered rows equal the dense path to float32 ulp, ungathered rows
    are EXACTLY zero; same contract as ``fleet_interval``). ``_n_total``
    is internal: the true fleet size when ``state`` is already a compact
    slice, so the active-fraction feature keeps its dense denominator."""
    if bw_ref is None:
        tab = _table_or_params(params, table)
        bw_ref = peak_bw(tab)
    if max_active is not None and max_active < state.threads.shape[0]:
        return _sparse_fleet_observe(params, state, flows=flows, spec=spec,
                                     objectives=objectives, bw_ref=bw_ref,
                                     max_active=max_active)
    free = (params.cap - state.buffers) / jnp.maximum(params.cap, 1e-9)
    parts = [jnp.concatenate([
        state.threads / params.n_max,
        state.throughputs / bw_ref,
        free,
    ], axis=-1)]  # (F, 8)
    if spec.context:
        prev = (state.prev_throughputs if state.prev_throughputs is not None
                else state.throughputs)
        delta = (state.throughputs - prev) / bw_ref
        drain = jnp.stack([
            (state.throughputs[:, 1] - state.throughputs[:, 0])
            * params.duration / jnp.maximum(params.cap[0], 1e-9),
            (state.throughputs[:, 2] - state.throughputs[:, 1])
            * params.duration / jnp.maximum(params.cap[1], 1e-9),
        ], axis=-1)
        parts += [delta, drain]
    if spec.fleet:
        n_flows = state.threads.shape[0]
        # the fraction denominator is the TRUE fleet size even on a
        # compact slice (same count in the numerator: every flow active
        # at the observe instant is inside the gather window)
        denom = n_flows if _n_total is None else _n_total
        act = active_at(flows, state.t)                  # (F,)
        net = state.throughputs[:, 1] * act              # (F,)
        agg = net.sum()
        frac = jnp.full((n_flows,), act.sum() / denom, jnp.float32)
        parts.append(jnp.stack([
            frac,
            jnp.full((n_flows,), agg / bw_ref, jnp.float32),
            net / jnp.maximum(agg, 1e-9),
        ], axis=-1))
    if spec.objectives:
        if objectives is None:
            objectives = default_objectives(state.threads.shape[0])
        parts.append(objective_features(
            objectives, state.t, _delivered_or_zeros(state),
            bw_ref=bw_ref, duration=params.duration))
    return jnp.concatenate(parts, axis=-1)


def jain_index(x, active=None, weights=None):
    """Jain's fairness index over the active entries of ``x``:
    (sum x)^2 / (n * sum x^2) — 1.0 at a perfectly even split, 1/n when one
    flow hoards everything. Empty or all-zero fleets score 1.0 (nothing is
    being shared unfairly).

    ``weights``: optional per-flow priority weights — the index is then
    taken over x/w, so "fair" means goodput PROPORTIONAL to priority (a
    gold flow holding 4x a bronze flow's rate scores 1.0). All-ones weights
    are the plain index bit-for-bit (x / 1.0 == x)."""
    x = jnp.asarray(x, jnp.float32)
    if weights is not None:
        x = x / jnp.asarray(weights, jnp.float32)
    if active is not None:
        x = x * active
        n = jnp.maximum(jnp.asarray(active, jnp.float32).sum(), 1.0)
    else:
        n = float(x.shape[-1])
    s = x.sum()
    s2 = (x * x).sum()
    return jnp.where(s2 > 0, s * s / (n * s2), 1.0)


def _fleet_reward(params: SimParams, tps, threads, act,
                  objs: FlowObjective, delivered0, t, bw_ref,
                  fairness_coef, deadline_coef):
    """The shared fleet objective — the ONE reward definition ``fleet_step``
    and ``topology_step`` score (they differ only in ``bw_ref``: the table
    peak vs the graph peak):

    reward = sum_f w_f * utility(t_f, n_f)
             - deadline_coef * sum_f w_f * miss_penalty_f
             + fairness_coef * Jain(goodput_f / w_f over active flows).

    The miss penalty judges this step's goodput against the rate the flow
    NEEDED at the step's start (remaining demand over remaining time, see
    repro.core.utility.deadline_penalty) — smooth in both rate and time, on
    only while the flow is active and its demand unmet."""
    need = needed_rate(objs.demand, delivered0, objs.deadline, t,
                       min_horizon=params.duration)
    miss_on = (jnp.isfinite(objs.deadline) & jnp.isfinite(objs.demand)
               & (objs.demand > delivered0))
    penalty = jnp.where(
        miss_on,
        deadline_penalty(tps[:, 2], need, scale=bw_ref) * act, 0.0)
    # the weighted utility is written base + correction — NOT sum(w*u) —
    # so the default-objective trace contains the identical reduce the
    # objective-free trace compiles (the correction, penalty, and weighted
    # Jain are all exact runtime zeros/no-ops at the defaults, keeping the
    # reward bit-identical across traces; XLA fuses a sum(w*u) reduce
    # differently and drifts an ulp)
    u = utility(tps, threads, k=params.k)
    return (u.sum()
            + ((objs.weight - 1.0) * u).sum()
            - deadline_coef * (objs.weight * penalty).sum()
            + fairness_coef * jain_index(tps[:, 2], act,
                                         weights=objs.weight))


@partial(jax.jit, static_argnames=("n_flows", "substeps", "spec", "backend",
                                   "max_active"))
def fleet_reset(params: SimParams, key, n_flows: int, t0=0.0, *,
                flows: FlowSchedule = None, table=None, substeps=50,
                spec: ObservationSpec = DEFAULT_OBS, backend="jnp",
                objectives: FlowObjective = None, max_active: int = None):
    """Random initial threads per flow (the same (F, 3) draw the single-flow
    reset makes for F=1 — identical key stream), empty buffers, one warm-up
    interval under contention for consistent observations. ``delivered``
    starts at zero: the warm-up interval is the pre-transfer probe, not
    progress against a deadline demand."""
    if flows is None:
        flows = always_on(n_flows)
    threads = jax.random.randint(key, (n_flows, 3), 1, 16).astype(jnp.float32)
    buffers = jnp.zeros((n_flows, 2), jnp.float32)
    t0 = jnp.asarray(t0, jnp.float32)
    buffers, tps = fleet_interval(params, buffers, threads, t0, flows=flows,
                                  table=table, substeps=substeps,
                                  backend=backend, objectives=objectives,
                                  max_active=max_active)
    return FleetState(buffers=buffers, threads=threads, throughputs=tps,
                      t=t0 + params.duration, prev_throughputs=tps,
                      delivered=jnp.zeros((n_flows,), jnp.float32))


@partial(jax.jit, static_argnames=("substeps", "spec", "backend",
                                   "max_active"))
def fleet_step(params: SimParams, state: FleetState, actions, *,
               flows: FlowSchedule = None, table=None, substeps=50,
               spec: ObservationSpec = DEFAULT_OBS, backend="jnp",
               fairness_coef=0.0, objectives: FlowObjective = None,
               deadline_coef=1.0, max_active: int = None):
    """actions: (F, 3) raw continuous -> round -> clamp [1, n_max], per flow.
    Returns (state', obs (F, frame_dim), reward scalar).

    reward = sum_f w_f * utility(t_f, n_f)
             - deadline_coef * sum_f w_f * miss_penalty_f
             + fairness_coef * Jain(goodput_f / w_f over active flows).

    Inactive flows move nothing, so their utility contribution is zero and
    they are masked out of the Jain term. fairness_coef and deadline_coef
    are TRACED operands (sweeping them never recompiles); at
    fairness_coef=0.0 the Jain term multiplies out to exactly +0.0
    (jain_index is always finite), so fairness_coef=0.0 with F=1 is the
    single-flow reward bit-for-bit. ``objectives=None`` (or the explicit
    ``default_objectives(F)``) is the objective-free PR 4 reward and
    contention split, also bit-for-bit: the miss penalty is masked to
    exactly 0.0 for flows without a finite deadline and demand.

    The miss penalty judges this step's goodput against the rate the flow
    NEEDED at the step's start (remaining demand over remaining time, see
    repro.core.utility.deadline_penalty) — smooth in both rate and time, on
    only while the flow is active and its demand unmet."""
    if flows is None:
        flows = always_on(state.threads.shape[0])
    threads = jnp.clip(jnp.round(actions), 1.0, params.n_max)
    tab = _table_or_params(params, table)
    bw_ref = peak_bw(tab)
    t_mid = state.t + 0.5 * params.duration
    sparse = max_active is not None and max_active < state.threads.shape[0]
    if sparse:
        # ONE gather serves both the solve and the reward: the reward's
        # act instant (t + duration/2) lies inside the interval window, so
        # every flow it can score nonzero is in the compact set; the fill
        # slots are exact no-ops through every reward term (tps 0, weight
        # 1, deadline inf, never active).
        (buffers, tps, idx, valid, c_tps, c_threads, c_flows,
         c_objs) = _sparse_fleet_interval(
            params, tab, state.buffers, threads, state.t, flows, substeps,
            backend, objectives, max_active, return_compact=True)
    else:
        buffers, tps = fleet_interval(params, state.buffers, threads,
                                      state.t, flows=flows, table=table,
                                      substeps=substeps, backend=backend,
                                      objectives=objectives,
                                      max_active=max_active)
    delivered0 = _delivered_or_zeros(state)
    new_state = FleetState(buffers=buffers, threads=threads, throughputs=tps,
                           t=state.t + params.duration,
                           prev_throughputs=state.throughputs,
                           delivered=delivered0 + tps[:, 2] * params.duration)
    if sparse:
        c_objs = default_objectives(max_active) if c_objs is None else c_objs
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
    obs = fleet_observe(params, new_state, flows=flows, table=table,
                        spec=spec, objectives=objectives,
                        max_active=max_active)
    return new_state, obs, reward


def fleet_achievable(params: SimParams, table, flows: FlowSchedule, t):
    """Best aggregate end-to-end rate the ACTIVE fleet could sustain at sim
    time ``t``: the slowest stage's scheduled cap, itself capped by what
    n_active * n_max threads can carry. The fleet-aware generalization of
    ``schedule.bottleneck_trace`` (0 when no flow is active)."""
    tab = _table_or_params(params, table)
    tpt, bw = schedule_at(tab, jnp.asarray(t, jnp.float32))
    n_act = active_at(flows, t).sum()
    return jnp.min(jnp.minimum(n_act * params.n_max * tpt, bw), axis=-1)
