"""Finds a cell's pieces by name: the cell in ``BENCHMARK.json``, its
configuration file, the driver that configuration names under
``bench/drivers/<name>.py``, its traffic file under
``bench/traffic/<name>.json``, and the readers of its per-layer metrics
under ``bench/metrics/<name>.py``. Adding a cell, a deployment or a
metric adds files and entries; nothing here changes."""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root=ROOT):
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench, name, root=ROOT):
    for c in bench["configs"]:
        if c["name"] == name:
            with open(Path(root) / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration named {name!r}")


def load_traffic(name, bench_dir=BENCH_DIR):
    path = Path(bench_dir) / "traffic" / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def metrics_for(bench, cell_name, kind):
    """The cell's metrics of ``kind`` ("end_to_end" or "per_layer"): each
    one that lists the cell, or lists no cells at all."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def load_reader(name, bench_dir=BENCH_DIR):
    """``read(ctx)`` of the per-layer metric ``name``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


DRIVER_FUNCTIONS = ("trainer", "reference", "faults", "round_flops",
                    "steps_per_round")


@functools.cache
def load_driver(name, bench_dir=BENCH_DIR):
    """The driver module ``name``: what one deployment kind is to the
    training harness (``harness.train``), as ``DRIVER_FUNCTIONS``. A name
    with no driver file, or a file without all of them, is an error. Each
    driver is loaded once in a process, so set-up, the reference and the
    counts all use the one module."""
    path = Path(bench_dir) / "drivers" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no driver named {name!r} ({path} does not exist)")
    spec = importlib.util.spec_from_file_location(
        "bench_driver_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in DRIVER_FUNCTIONS if not callable(getattr(mod, f,
                                                                   None))]
    if missing:
        raise KeyError(f"driver {name!r} lacks {missing}")
    return mod


def driver_of(config):
    """The driver the configuration names in its ``"driver"`` key; there
    is no default."""
    if "driver" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"\"driver\"")
    return load_driver(config["driver"])


def load_peaks(kind, bench_dir=BENCH_DIR):
    """Published peaks of one chip of ``device_kind`` ``kind``; a kind the
    table does not hold is an error, not a default."""
    with open(Path(bench_dir) / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["kinds"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table['kinds'])})")
    return table["kinds"][kind]
