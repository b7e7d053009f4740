"""Readings that the limits of ``correct`` are set from; not part of a
benchmark run.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out chiprun_out/calib.jsonl]

For each seed it drives the cell's timed path through set-up exactly as
``run.py`` does and prints the numbers compared against the reference at
the configuration's precision (``program``). On the control seeds it also
puts the reference in the program's place: computed with float8 operands
(``control_float8``), at float32 HIGHEST (``witness_f32_highest``), and
with each fault that the configuration's driver names in ``faults``
planted (``fault_<name>``; the single-flow driver's are ``half_batch``,
``state_unchanged`` and ``reward_altered``). One JSON object per line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from harness import check, spec, train  # noqa: E402
from harness.device import enable_compile_cache, require_chips  # noqa: E402
from harness.trace import Spans  # noqa: E402


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def env_mismatch(prog, ref, r=0, rel=1e-4):
    """Share of round ``r``'s envs whose episode reward departs from the
    reference by more than ``rel``: an env whose every rounded action
    agreed with the reference's reads the same reward to float noise."""
    a, b = np.asarray(prog["rewards"][r]), np.asarray(ref["rewards"][r])
    return float(np.mean(np.abs(a - b) > rel * np.maximum(np.abs(b), 1e-30)))


def readings(prog, ref):
    """The numbers compared, and beside them what the look behind the
    limits rests on: the median leaves, every leaf by name, the mean
    reward, the share of envs whose episode left the reference's in each
    round, both sides' losses."""
    g = check.leaf_gaps(prog["m0"], ref["m0"])
    v = check.leaf_gaps(prog["v0"], ref["v0"])
    dp, dr, moving = check.param_changes(prog, ref)
    u = check.leaf_gaps(dp, dr, moving)

    def top(d):
        return sorted(d.items(), key=lambda kv: -kv[1])[:4]
    return {**check.train_numbers(prog, ref),
            "grad_median": float(np.median(list(g.values()))),
            "moment_median": float(np.median(list(v.values()))),
            "update_median": float(np.median(list(u.values()))),
            "reward_gap": max(check.rel(a, b) for a, b in
                              zip(prog["reward_mean"], ref["reward_mean"])),
            "env_mismatch_r0": env_mismatch(prog, ref, 0),
            "env_mismatch_r1": env_mismatch(prog, ref, 1),
            "env_mismatch_r2": env_mismatch(prog, ref, 2),
            "n_moving": len(moving), "n_leaves": len(check.leaf_norms(dr)),
            "grad_top": top(g), "update_top": top(u),
            "grad_leaves": g, "moment_leaves": v, "update_leaves": u,
            "loss": prog["loss"], "ref_loss": ref["loss"],
            "reward": prog["reward_mean"], "ref_reward": ref["reward_mean"]}


def seed_readings(config, traffic, seed, controls):
    rounds = train.run_program(config, traffic, seed, 0.0, Spans())
    prog = train.program_numbers(rounds)
    del rounds
    ref = train.reference(config, traffic, seed)
    yield "program", readings(prog, ref)
    if seed not in controls:
        return
    faults = spec.driver_of(config).faults(config, traffic)
    for kind, kw in (("control_float8", {"dtype": "float8"}),
                     ("witness_f32_highest", {"dtype": "float32"}),
                     *((f"fault_{name}", kw) for name, kw in faults.items())):
        other = train.reference(config, traffic, seed, **kw)
        yield kind, readings(other, ref)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    config = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    require_chips(cell["chips"])
    enable_compile_cache()
    with (open(args.out, "a") if args.out
          else contextlib.nullcontext()) as out:
        for seed in args.seeds:
            t = time.perf_counter()
            for kind, nums in seed_readings(config, traffic, seed,
                                            set(args.control_seeds)):
                line = json.dumps({"cell": cell["name"], "seed": seed,
                                   "kind": kind, **nums,
                                   "s": time.perf_counter() - t})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
