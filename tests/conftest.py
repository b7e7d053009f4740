import os
import time

# Tests run on the single real CPU device (the 512-device fake platform is
# ONLY for the dry-run, set inside repro.launch.dryrun before jax init).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# 8 host devices let the sharding/elastic tests build small real meshes while
# staying cheap; model smoke tests ignore the extra devices.

# Tier-1 wall-clock budget (seconds): the default tier-1 selection
# (`-m "not slow"`, from pytest.ini addopts) FAILS if the whole session runs
# longer — keeps the suite honest about what belongs behind the slow marker.
TIER1_BUDGET_S = float(os.environ.get("TIER1_BUDGET_S", "900"))

# Per-TEST budget (seconds): any single tier-1 test call exceeding this
# fails the run and is named — so when the session guard trips, the report
# points at the culprit instead of the whole suite. (CI also publishes
# --durations=25 + a junit XML artifact for the full ranking.)
TIER1_TEST_BUDGET_S = float(os.environ.get("TIER1_TEST_BUDGET_S", "120"))

_session_t0 = None
_over_budget = []  # (nodeid, seconds) of tests past TIER1_TEST_BUDGET_S


def _is_tier1_selection(config) -> bool:
    markexpr = getattr(config.option, "markexpr", "") or ""
    return "not slow" in markexpr


def pytest_configure(config):
    global _session_t0
    _session_t0 = time.monotonic()


def pytest_runtest_logreport(report):
    if report.when == "call" and report.duration > TIER1_TEST_BUDGET_S:
        _over_budget.append((report.nodeid, report.duration))


def _report(session, msg):
    reporter = session.config.pluginmanager.get_plugin("terminalreporter")
    if reporter is not None:
        reporter.write_line(msg, red=True)
    else:  # pragma: no cover
        print(msg)


def pytest_sessionfinish(session, exitstatus):
    if _session_t0 is None or not _is_tier1_selection(session.config):
        return
    if _over_budget and exitstatus == 0:
        session.exitstatus = 1
        for nodeid, dur in sorted(_over_budget, key=lambda x: -x[1]):
            _report(session,
                    f"tier-1 per-test guard: {nodeid} took {dur:.0f}s "
                    f"(> {TIER1_TEST_BUDGET_S:.0f}s; TIER1_TEST_BUDGET_S "
                    "to adjust, or move it behind the `slow` marker)")
    elapsed = time.monotonic() - _session_t0
    if elapsed > TIER1_BUDGET_S and exitstatus == 0:
        session.exitstatus = 1
        _report(session,
                f"tier-1 wall-clock guard: {elapsed:.0f}s exceeds the "
                f"{TIER1_BUDGET_S:.0f}s budget (TIER1_BUDGET_S to adjust; "
                "move long tests behind the `slow` marker)")
