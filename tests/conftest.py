"""Shared fixtures.

Mirrors the reference's conftest strategy (``python/ray/tests/conftest.py``):
``ray_start_regular`` boots a real single-node runtime per test; ``ray_start_cluster``
boots a multi-agent cluster in one machine (reference :410/:491).  For jax tests, a
virtual 8-device CPU mesh stands in for a TPU slice (SURVEY §4 takeaway: a fake
mesh/ICI backend so multi-host pjit paths run in CI without TPUs).
"""

import os

from ray_tpu.utils.testing import CPU_WORKER_ENV, force_cpu_devices

# Force the 8-device virtual CPU mesh before any jax backend use: the tests
# run on the CPU whatever the host holds, and never claim a chip.
force_cpu_devices(8)

import signal  # noqa: E402

import pytest  # noqa: E402

# the tests' shared modules assert too: show their operands as a test's own
pytest.register_assert_rewrite("contract", "chip_compile")

from kinds import kind, tiny_doc, tiny_model  # noqa: E402,F401  (fixtures)

# Per-test timeout (reference: pytest.ini's 180 s pytest-timeout default).
# pytest-timeout isn't in this image, so a SIGALRM in the main thread stands
# in: a wedged test raises instead of hanging the whole suite forever.
_TEST_TIMEOUT_S = int(os.environ.get("RAYTPU_TEST_TIMEOUT_S", "180"))


def _alarm_guard(item, phase_timeout):
    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded {phase_timeout}s per-phase timeout "
            f"(conftest SIGALRM)")

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    # REPEATING timer, not a one-shot alarm: if the first TimeoutError is
    # swallowed by a broad `except` in a wedged teardown and the code blocks
    # again, a later fire converts the would-be permanent suite hang into
    # another raise that eventually propagates (seen once: a contended run
    # deadlocked for 40+ min after a failure, all threads in futex_wait).
    signal.setitimer(signal.ITIMER_REAL, phase_timeout, 30.0)
    return prev


def _item_timeout(item) -> int:
    m = item.get_closest_marker("timeout")
    return int(m.args[0]) if m else _TEST_TIMEOUT_S


# Guard all three phases — cluster boot/shutdown happens in fixture
# setup/teardown, which can wedge just as hard as the test body.
@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    prev = _alarm_guard(item, _item_timeout(item))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    prev = _alarm_guard(item, _item_timeout(item))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    prev = _alarm_guard(item, _item_timeout(item))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


# ---------------------------------------------------------------------------
# Tier-1 wall-time guard (CI tooling): the verify window is a fixed budget
# (ROADMAP: 870 s for the whole non-slow suite), and one unmarked test
# quietly growing past a couple of minutes is how the window dies.  Any
# test NOT marked ``slow`` whose call phase exceeds the per-test budget
# fails the SESSION at exit (the test itself still reports its own
# outcome), naming the offenders — mark them ``slow`` or split them.
# default 120 s: the slowest tier-1 test at PR 13 ran 16.4 s, so the
# budget is ~7x headroom — enough for box noise, tight enough that a
# runaway test fails loudly long before it eats the verify window
_TIER1_TEST_BUDGET_S = float(os.environ.get("RAYTPU_TIER1_TEST_BUDGET_S",
                                            "120"))
_tier1_overruns: list = []


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if (report.when == "call" and _TIER1_TEST_BUDGET_S > 0
            and report.duration > _TIER1_TEST_BUDGET_S
            and item.get_closest_marker("slow") is None):
        _tier1_overruns.append((item.nodeid, report.duration))


def pytest_sessionfinish(session, exitstatus):
    if not _tier1_overruns:
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    lines = [f"  {nodeid}: {dur:.1f}s > {_TIER1_TEST_BUDGET_S:.0f}s budget"
             for nodeid, dur in _tier1_overruns]
    msg = ("tier-1 per-test wall-time budget exceeded (mark these slow, "
           "split them, or raise RAYTPU_TIER1_TEST_BUDGET_S):\n"
           + "\n".join(lines))
    if tr is not None:
        tr.write_sep("=", "tier-1 wall-time guard", red=True)
        tr.write_line(msg)
    if session.exitstatus == 0:
        session.exitstatus = 1


def pytest_generate_tests(metafunc):
    """A contract test's cases are its class's (``contract.cases``)."""
    marked = getattr(metafunc.function, "class_cases", None)
    if marked and metafunc.cls is not None:
        arg, of_class = marked
        rows = of_class(metafunc.cls)
        metafunc.parametrize(arg, [r[1:] for r in rows],
                             ids=[r[0] for r in rows])


@pytest.fixture
def ray_start_regular():
    import ray_tpu
    info = ray_tpu.init(num_cpus=4, worker_env=dict(CPU_WORKER_ENV))
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.core.cluster import Cluster
    cluster = Cluster(initialize_head=False)
    yield cluster
    import ray_tpu
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs
