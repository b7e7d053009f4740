"""The numbers that decide ``correct``, each held to the limit its traffic
file states (set from sound runs, the float8 control and planted faults,
see PERF.md), and how a run prints them."""

from __future__ import annotations

import json
import math
import sys

import numpy as np


def leaf_norms(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64).ravel())) for k, v in flat}


def leaf_gaps(prog, ref, leaves=None):
    """Per leaf, |norm_prog - norm_ref| against the larger of the
    reference's norm of that leaf and of the median leaf."""
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    keys = [k for k in rn if leaves is None or k in leaves]
    med = float(np.median([rn[k] for k in keys]))
    return {k: abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30) for k in keys}


def moving_leaves(ref_grad, frac=1e-3):
    """Leaves whose reference gradient is at least ``frac`` of the median
    leaf's: the others move under Adam by round-off alone."""
    rn = leaf_norms(ref_grad)
    med = float(np.median(list(rn.values())))
    return {k for k, v in rn.items() if v >= frac * med}


def rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def train_numbers(prog, ref):
    """``prog``/``ref``: dicts with per-round ``loss`` lists, ``m0`` and
    ``v0`` (Adam's first and second moments after round 0), ``p0`` and
    ``p3`` (params before round 0 and after round 2). Each step's loss,
    and the first round's loss alone; the gradient as Adam holds it, its
    square as Adam holds it, and the change over the three rounds, each by
    its worst leaf."""
    dp, dr, moving = param_changes(prog, ref)
    return {
        "loss_gap": max(rel(a, b) for a, b in zip(prog["loss"],
                                                   ref["loss"])),
        "loss_gap_r0": rel(prog["loss"][0], ref["loss"][0]),
        "grad_worst": max(leaf_gaps(prog["m0"], ref["m0"]).values()),
        "moment_worst": max(leaf_gaps(prog["v0"], ref["v0"]).values()),
        "update_worst": max(leaf_gaps(dp, dr, moving).values()),
    }


def param_changes(prog, ref):
    """Both sides' change of the params over the checked rounds, and the
    leaves that count (``moving_leaves`` of the reference's gradient)."""
    import jax
    moving = moving_leaves(ref["m0"])
    dp = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                      - np.asarray(b, np.float64), prog["p3"], prog["p0"])
    dr = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                      - np.asarray(b, np.float64), ref["p3"], ref["p0"])
    return dp, dr, moving


def judge(numbers, limits):
    """(correct, {name: {"value", "limit"}}) over the numbers the traffic
    file gives a limit: every one finite and at or under its limit."""
    checks = {k: {"value": float(numbers[k]), "limit": float(lim)}
              for k, lim in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def emit(result, checks):
    """Print the numbers compared on stderr as the last lines there, then
    the result line (with ``checks`` as its last key) on stdout."""
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
