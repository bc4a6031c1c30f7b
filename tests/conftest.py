"""Shared fixtures.

Mirrors the reference's conftest strategy (``python/ray/tests/conftest.py``):
``ray_start_regular`` boots a real single-node runtime per test; ``ray_start_cluster``
boots a multi-agent cluster in one machine (reference :410/:491).  For jax tests, a
virtual 8-device CPU mesh stands in for a TPU slice (SURVEY §4 takeaway: a fake
mesh/ICI backend so multi-host pjit paths run in CI without TPUs).
"""

from ray_tpu.utils.testing import CPU_WORKER_ENV, force_cpu_devices

# Force the 8-device virtual CPU mesh before any jax backend use: the tests
# run on the CPU whatever the host holds, and never claim a chip.
force_cpu_devices(8)

import signal  # noqa: E402

import pytest  # noqa: E402

# the tests' shared modules assert too: show their operands as a test's own
pytest.register_assert_rewrite("contract", "chip_compile")

from kinds import kind, tiny_doc, tiny_model  # noqa: E402,F401  (fixtures)

# One limit, one meaning (pytest-timeout isn't in this image; a SIGALRM in
# the main thread stands in).  ``@pytest.mark.timeout(N)`` bounds the CALL
# alone.  Set-up keeps 180 s whatever the mark says: a file's first test may
# build a session-scoped tiny model or compile its programs there
# (tests/kinds.py).  Teardown gets 60 s: ``shutdown``'s stated waits sum to
# 42 s (core/api.py 1.5 + 2 + 5 + 30 + 5 s; Cluster.shutdown 5 s a node).
_PHASE_LIMIT_S = {"setup": 180, "call": 180, "teardown": 60}
_RUNTIME_FIXTURES = {"ray_start_regular", "ray_start_cluster"}
_wedged_by = None  # nodeid whose runtime set-up/teardown ran into its limit


def _asks_for_runtime(item) -> bool:
    if _RUNTIME_FIXTURES & set(item.fixturenames):
        return True
    boots = getattr(item.module, "_boots_runtime", None)
    if boots is None:  # the 13 files that boot one with their own init/Cluster
        with open(item.module.__file__) as f:
            text = f.read()
        boots = item.module._boots_runtime = (
            "ray_tpu.init(" in text or "Cluster(" in text)
    return boots


def _guarded_phase(item, when):
    """Bound one phase of ``item`` (hookwrapper body).  A timed-out phase
    fails once, with every thread's stack in the error's text (junit and the
    log both hold it); a runtime test cut in set-up or teardown marks this
    worker process wedged, and later runtime tests fail at once, naming it."""
    if when == "setup" and _wedged_by and _asks_for_runtime(item):
        pytest.fail(f"runtime left wedged by {_wedged_by}", pytrace=False)
    limit = _PHASE_LIMIT_S[when]
    mark = item.get_closest_marker("timeout")
    if when == "call" and mark:
        limit = int(mark.args[0])

    def _on_alarm(signum, frame):
        global _wedged_by
        if when != "call" and _wedged_by is None and _asks_for_runtime(item):
            _wedged_by = item.nodeid
        from ray_tpu.util.debug import dump_all_stacks
        raise TimeoutError(
            f"{item.nodeid} exceeded its {limit}s {when} limit (conftest "
            f"SIGALRM); all threads:\n{dump_all_stacks()}")

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    # REPEATING timer, not a one-shot alarm: if the first TimeoutError is
    # swallowed by a broad `except` in a wedged teardown and the code blocks
    # again, a later fire converts the would-be permanent suite hang into
    # another raise that eventually propagates (seen once: a contended run
    # deadlocked for 40+ min after a failure, all threads in futex_wait).
    signal.setitimer(signal.ITIMER_REAL, limit, 30.0)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


# All three phases — cluster boot/shutdown happens in fixture
# setup/teardown, which can wedge just as hard as the test body.
@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _guarded_phase(item, "setup")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _guarded_phase(item, "call")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _guarded_phase(item, "teardown")


# ---------------------------------------------------------------------------
# Tier-1 wall-time guard (CI tooling): the verify window is a fixed budget
# (the driver's six-worker command, /root/TESTS_LAST_RUN.json: 1,470 s), and
# one unmarked test quietly growing past a couple of minutes is how the
# window dies.  Any test NOT marked ``slow`` whose call phase exceeds the
# per-test budget fails the SESSION at exit (the test itself still reports
# its own outcome), naming the offenders — mark them ``slow`` or split them.
# default 120 s: the slowest tier-1 test at PR 13 ran 16.4 s, so the
# budget is ~7x headroom — enough for box noise, tight enough that a
# runaway test fails loudly long before it eats the verify window
_TIER1_TEST_BUDGET_S = 120.0
_tier1_overruns: list = []


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if (report.when == "call" and report.duration > _TIER1_TEST_BUDGET_S
            and item.get_closest_marker("slow") is None):
        _tier1_overruns.append((item.nodeid, report.duration))


def pytest_sessionfinish(session, exitstatus):
    if not _tier1_overruns:
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    lines = [f"  {nodeid}: {dur:.1f}s > {_TIER1_TEST_BUDGET_S:.0f}s budget"
             for nodeid, dur in _tier1_overruns]
    msg = ("tier-1 per-test wall-time budget exceeded (mark these slow, "
           "or split them):\n" + "\n".join(lines))
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
