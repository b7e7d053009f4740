"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  Fig. 3  -> bench_convergence     (completion time vs Marlin)
  Fig. 4  -> bench_action_space    (discrete vs continuous actions)
  Fig. 5  -> bench_bottleneck      (3 bottleneck scenarios, stability)
  Table I -> bench_end_to_end      (Globus/Marlin/AutoMDT, live engine;
                                    + per-family live ScenarioDriver replays:
                                    end_to_end.scenario_live.*.utilization)
  §V-A    -> bench_training_time   (offline training wall time; + substep
                                    backend comparison jnp vs pallas and
                                    per-policy episode cost mlp/stacked/gru)
  (g)     -> roofline              (dry-run roofline aggregates)
  beyond  -> bench_scenarios       (dynamic conditions: schedule-context
                                    domain-randomized agent vs base-obs
                                    agent and static/exploration-only, plus
                                    the temporal policy stack mlp vs
                                    stacked vs gru)
  beyond  -> bench_fleet           (multi-flow fleet: shared fairness-aware
                                    policy vs per-flow-independent AutoMDT/
                                    static/Marlin across arrival families —
                                    aggregate utilization + Jain index)
  beyond  -> bench_objectives      (heterogeneous flow objectives: the
                                    objective-aware shared policy + enforced
                                    rate floors vs objective-blind AutoMDT/
                                    static/Marlin on mixed gold/bronze
                                    scenarios — deadline-hit-rate + weighted
                                    utilization)
  beyond  -> bench_topology        (multi-link topology: the topology-aware
                                    shared policy vs the single-bottleneck
                                    fleet policy and per-flow static across
                                    regional_diurnal / link_failover /
                                    cross_traffic — aggregate utilization +
                                    Jain + failover recovery time)
  beyond  -> bench_faults          (failure & recovery: the fault-trained
                                    fleet policy vs frozen fault-blind and
                                    static baselines under seeded
                                    kill/restart + stage-hang schedules —
                                    post-failure recovery time, completion
                                    time, deadline hit-rate)
  beyond  -> bench_controller      (live-path scale-out: per-interval
                                    FleetController cost at F up to 4096,
                                    per-flow Python loop baseline vs the
                                    array-native one-dispatch path, plus
                                    full sim step dense vs sparse with
                                    observe+reward included)
  beyond  -> bench_online          (hybrid offline/online: the frozen
                                    fleet policy + the online residual
                                    head vs frozen-only and static on a
                                    held-out condition family — post-
                                    collapse recovery time + integrated
                                    recovery deficit)

``--quick`` runs the CI smoke subset: the substep-backend and per-policy
episode-cost microbenches plus bench_scenarios, bench_fleet,
bench_objectives, bench_topology, bench_faults, bench_controller, and
bench_online in quick mode (tiny training budgets) — minutes, not the
full suite, so CI catches perf entry points that rot without paying for
the real numbers.

``--suite NAME[,NAME...]`` runs only the named suite(s) from the selected
set (quick names with ``--quick``, full names otherwise) — e.g.
``run.py --quick --suite controller_scaling_quick`` re-measures one suite
without paying for the rest. Unknown names fail fast, listing what's
available.

``--json PATH`` additionally writes every row to PATH as JSON — CI uploads
the quick rows as a ``BENCH_<pr>.json`` artifact per PR, the repo's
benchmark trajectory (see README).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; add the root so the `benchmarks.*` imports resolve no matter
# where the script is launched from.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    quick = "--quick" in argv
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
            sys.exit("usage: run.py [--quick] [--json PATH] "
                     "[--suite NAME[,NAME...]]")
        json_path = argv[i + 1]
    only = None
    if "--suite" in argv:
        i = argv.index("--suite")
        if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
            sys.exit("usage: run.py [--quick] [--json PATH] "
                     "[--suite NAME[,NAME...]]")
        only = [s for s in argv[i + 1].split(",") if s]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_training_time, bench_convergence,
                            bench_bottleneck, bench_action_space,
                            bench_end_to_end, bench_finetune, roofline,
                            bench_scenarios, bench_fleet, bench_objectives,
                            bench_topology, bench_faults, bench_controller,
                            bench_online)
    if quick:
        suites = [
            ("training_time_backends",
             lambda rows: bench_training_time.backend_rows(rows, n_envs=8,
                                                           iters=3)),
            ("training_time_policies",
             lambda rows: bench_training_time.policy_rows(rows, n_envs=4,
                                                          iters=2)),
            ("fleet_scaling_quick",
             lambda rows: bench_training_time.fleet_scaling_rows(
                 rows, iters=2, pallas_max_f=64)),
            ("scenarios_quick",
             lambda rows: bench_scenarios.main(rows, quick=True)),
            ("fleet_quick",
             lambda rows: bench_fleet.main(rows, quick=True)),
            ("objectives_quick",
             lambda rows: bench_objectives.main(rows, quick=True)),
            ("topology_quick",
             lambda rows: bench_topology.main(rows, quick=True)),
            ("faults_quick",
             lambda rows: bench_faults.main(rows, quick=True)),
            ("controller_scaling_quick",
             lambda rows: bench_controller.controller_scaling(rows,
                                                              quick=True)),
            ("online_quick",
             lambda rows: bench_online.main(rows, quick=True)),
        ]
    else:
        suites = [
            ("training_time", bench_training_time.main),
            ("fleet_scaling", bench_training_time.fleet_scaling_rows),
            ("convergence", bench_convergence.main),
            ("bottleneck", bench_bottleneck.main),
            ("action_space", bench_action_space.main),
            ("end_to_end", bench_end_to_end.main),
            ("finetune", bench_finetune.main),
            ("roofline", roofline.main),
            ("scenarios", bench_scenarios.main),
            ("fleet", bench_fleet.main),
            ("objectives", bench_objectives.main),
            ("topology", bench_topology.main),
            ("faults", bench_faults.main),
            ("controller_scaling", bench_controller.controller_scaling),
            ("online", bench_online.main),
        ]
    if only is not None:
        known = {n for n, _ in suites}
        bad = [s for s in only if s not in known]
        if bad:
            sys.exit(f"run.py: unknown suite(s) {', '.join(bad)} — "
                     f"available: {', '.join(sorted(known))}")
        suites = [(n, fn) for n, fn in suites if n in only]
    print("name,us_per_call,derived")
    failed = []
    all_rows = []

    def emit(rows):
        for r in rows:
            n, us, derived = r
            print(f"{n},{us:.1f},{str(derived).replace(',', ';')}")
            all_rows.append({"name": n, "us_per_call": float(us),
                             "derived": str(derived)})

    for name, fn in suites:
        t0 = time.time()
        # the sub-bench MUTATES this list, so the rows it produced before
        # an exception survive — a crash mid-suite loses the suite, not
        # the measurements already taken
        rows = []
        try:
            ret = fn(rows)
            emit(ret if ret is not None else rows)
            wall = time.time() - t0
            print(f"suite.{name}.wall_s,{wall * 1e6:.0f},{wall:.1f}s",
                  flush=True)
            all_rows.append({"name": f"suite.{name}.wall_s",
                             "us_per_call": wall * 1e6,
                             "derived": f"{wall:.1f}s"})
        except Exception:
            failed.append(name)
            emit(rows)  # partial rows, loudly marked below
            print(f"suite.{name}.FAILED,0,{traceback.format_exc(limit=1)!r}",
                  flush=True)
            all_rows.append({"name": f"suite.{name}.FAILED",
                             "us_per_call": 0.0,
                             "derived": traceback.format_exc(limit=1)})
            traceback.print_exc(file=sys.stderr)
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"quick": quick, "failures": len(failed),
                       "rows": all_rows}, f, indent=1)
        print(f"suite.json_written,0,{json_path}", flush=True)
    if failed:
        print(f"run.py: {len(failed)} suite(s) FAILED: {', '.join(failed)}",
              file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
