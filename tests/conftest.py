"""Test harness: eight virtual CPU devices (SURVEY.md §5.2).

``reexec_cpu.py`` (the early plugin ``pytest.ini`` loads) has normally set
the environment already; this conftest calls it again for invocations
that bypass ``pytest.ini`` (e.g. a different rootdir). Either way it runs
before jax is imported, which is when the device count is read.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import reexec_cpu  # noqa: E402

reexec_cpu.use_cpu_mesh()

import jax  # noqa: E402
import pytest  # noqa: E402

# Do NOT enable the persistent XLA compile cache here, tempting as the
# compile-dominated suite wall is: reloading a cached executable for the
# virtual 8-device CPU mesh has aborted the process (XLA CHECK failure
# inside the second build of a donated-args SPMD step; reproduced on
# tests/test_asyncsgd.py::test_spmd_checkpoint_resume with a same-run,
# same-platform cache). bench.py's cache stays safe because bench never
# rebuilds an identical step inside one process.


# ---------------------------------------------------------------------------
# Tier-1 wall-time guard (ISSUE 13 satellite). The tier-1 driver kills
# the suite at a hard 870 s; the budget was already breached once (PR 8
# HEAD) and the failure mode is a silent timeout-kill — the run just
# dies, with no record of which tests grew. This plugin makes the
# regression visible INSIDE the suite: every run prints wall vs budget
# plus the slowest tests, and a default-tier run (``-m "not slow"``,
# the driver-timed shape) whose wall projects past the budget FAILS
# loudly here, where the offending tests are named, before the driver's
# kill eats the cap. Override the budget with MPIT_T1_BUDGET_S; the
# failure threshold is 92% of it (the remaining 8% is collection +
# teardown + machine variance headroom).
# ---------------------------------------------------------------------------

import time as _time

_T1_GUARD: dict = {"t0": None, "durations": []}
_T1_FAIL_FRACTION = 0.92


def _t1_budget_s() -> float:
    return float(os.environ.get("MPIT_T1_BUDGET_S", "870"))


def _t1_is_default_tier(config) -> bool:
    """Only the driver-timed shape fails on projection: the marker
    expression excludes slow tests and nothing re-includes them."""
    expr = config.getoption("-m", default="") or ""
    return "not slow" in expr and "slow or" not in expr


def pytest_sessionstart(session):
    _T1_GUARD["t0"] = _time.time()
    _T1_GUARD["durations"] = []


def pytest_runtest_logreport(report):
    if report.when == "call":
        _T1_GUARD["durations"].append((report.duration, report.nodeid))


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if _T1_GUARD["t0"] is None:
        return
    wall = _time.time() - _T1_GUARD["t0"]
    budget = _t1_budget_s()
    frac = wall / budget
    tr = terminalreporter
    tr.section("tier-1 wall-time guard")
    tr.line(
        f"suite wall {wall:.1f}s of {budget:.0f}s budget "
        f"({100 * frac:.0f}%); fails past "
        f"{100 * _T1_FAIL_FRACTION:.0f}% on the default tier"
    )
    slowest = sorted(_T1_GUARD["durations"], reverse=True)[:10]
    for dur, nodeid in slowest:
        tr.line(f"  {dur:7.2f}s  {nodeid}")
    if frac > _T1_FAIL_FRACTION and _t1_is_default_tier(config):
        tr.line(
            "TIER-1 WALL-TIME BUDGET PROJECTED EXCEEDED: trim or mark "
            "`slow` the tests above (the driver hard-kills at "
            f"{budget:.0f}s and records nothing).",
            red=True,
            bold=True,
        )


def pytest_sessionfinish(session, exitstatus):
    if _T1_GUARD["t0"] is None:
        return
    wall = _time.time() - _T1_GUARD["t0"]
    if (
        wall / _t1_budget_s() > _T1_FAIL_FRACTION
        and _t1_is_default_tier(session.config)
        and exitstatus == 0
    ):
        # Loud failure while the suite can still name the culprits —
        # wrap_session returns session.exitstatus, so this flips the
        # run red without touching any test's own verdict.
        session.exitstatus = 1


# ---------------------------------------------------------------------------
# Lock-order auditor (ISSUE 14 satellite): the threaded suites run with
# mpit_tpu.analysis.lockdep enabled — every lock created by package code
# is recorded, and a test whose run produces a cycle in the lock-order
# graph (two locks ever taken in both orders = a latent deadlock,
# whether or not this run interleaved into it) FAILS with the cycle
# named. Scoped to the suites that actually exercise the host
# concurrency layer; everything else pays nothing.
# ---------------------------------------------------------------------------

_LOCKDEP_SUITES = {"test_compat.py", "test_elastic.py"}


@pytest.fixture(autouse=True)
def _lockdep_threaded_suites(request):
    if os.path.basename(str(request.node.fspath)) not in _LOCKDEP_SUITES:
        yield
        return
    from mpit_tpu.analysis import lockdep

    lockdep.install()
    lockdep.reset()
    try:
        yield
        cycles = lockdep.cycles()
        if cycles:
            pytest.fail(
                "lock-order cycle recorded during this test "
                "(latent deadlock):\n" + lockdep.format_cycles(cycles)
            )
    finally:
        lockdep.reset()
        lockdep.uninstall()


@pytest.fixture(scope="session")
def n_devices() -> int:
    return jax.device_count()


@pytest.fixture()
def world8():
    """A fresh pure-DP World over all (8 fake) devices."""
    from mpit_tpu import comm

    return comm.init()


@pytest.fixture()
def world_2d():
    """A 2-D (data=4, model=2) World for mixed-parallelism tests."""
    from mpit_tpu import comm

    return comm.init({"data": 4, "model": 2}, set_default=False)


@pytest.fixture(scope="session")
def v5e_world():
    """A pure-DP World over a *described* v5e:2x4 (device proxies, no
    hardware) for the real-compiler tests; skips where this installation
    cannot describe the topology."""
    from mpit_tpu.utils.aot import topology_world

    try:
        return topology_world({"data": 8}, "v5e:2x4")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"v5e topology cannot be described: {e}")


def require_devices(n: int):
    """Skip marker helper for tests needing at least n devices."""
    return pytest.mark.skipif(
        jax.device_count() < n, reason=f"needs >= {n} devices"
    )
