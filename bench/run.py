"""Run one cell of the benchmark once, on the chip this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic file;
``harness.train`` drives the program through them. Set-up warms every
shape the window uses; the window then runs for ``--seconds``; the plain
reference checks what the window's program produced. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last the numbers compared with their limits
(``checks``), which also end standard error.

A host whose first JAX device is not a TPU, or that has fewer chips than
the cell asks for, exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import check, spec, train  # noqa: E402
from harness.device import (CompileCounter, NoChip, describe,  # noqa: E402
                            enable_compile_cache, require_chips, warn)
from harness.trace import Spans, Tracer  # noqa: E402

OUT_DIR = ROOT / ".bench_out"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(bench, cell, ctx, dtrace, peaks):
    view = SimpleNamespace(trace=dtrace, peaks=peaks, **ctx)
    out = {}
    for m in spec.metrics_for(bench, cell["name"], "per_layer"):
        v = spec.load_reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None, *, need_chip=True):
    t_start = time.perf_counter()
    args = parse(argv)
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    config = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    import jax
    try:
        devices = (require_chips(cell["chips"]) if need_chip
                   else jax.devices()[:cell["chips"]])
    except NoChip as e:
        warn(f"bench/run.py: {e}")
        return 2
    enable_compile_cache()
    compiles = CompileCounter()
    traced = bool(args.trace)
    spans = Spans(annotate=traced)
    tracer = (Tracer(OUT_DIR / f"trace-{cell['name']}",
                     traffic.get("trace_seconds")) if traced else None)
    seconds = tracer.seconds(args.seconds) if traced else args.seconds
    e2e, ctx, prog, extra, counts = train.run(
        config, traffic, args.seed, seconds, spans, tracer, compiles,
        t_start)
    device = describe(devices, extra)
    gc.collect()
    if counts["compiles_in_window"]:
        warn(f"{counts['compiles_in_window']} compilations inside the "
             f"measured window")
    numbers = train.numbers(config, traffic, args.seed, prog)
    correct, checks = check.judge(numbers, traffic["limits"])
    result = {"correct": correct, "attempted": counts["attempted"],
              "failed": counts["failed"]}
    if traced:
        dtrace = tracer.read()
        for line in dtrace.describe():
            warn(f"trace: {line}")
        peaks = spec.load_peaks(devices[0].device_kind) if need_chip else {}
        result["metrics"] = per_layer(bench, cell, ctx, dtrace, peaks)
        device["busy_s"] = dtrace.busy_s()
        device["window_s"] = dtrace.window_s
        result["device"] = device
        result["breakdown"] = dtrace.breakdown()
    else:
        names = {m["name"]: m["unit"]
                 for m in spec.metrics_for(bench, cell["name"],
                                           "end_to_end")}
        result["metrics"] = {k: {"value": float(v), "unit": names[k]}
                             for k, v in e2e.items() if k in names}
        result["device"] = device
    check.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
