"""The tests' own guard (tests/conftest.py), held to what it promises: a
phase that hangs fails once, in its own limit, with every thread's stack; the
mark bounds the call alone; a worker whose runtime teardown was cut says so
to every later runtime test at once and stays up.

One pytest of two xdist workers runs three hung files in a temporary
directory whose conftest is the repo's (teardown's limit shortened to 2 s);
the cases read its junit file.
"""

import os
import subprocess
import sys
import textwrap
import xml.etree.ElementTree as ET

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)

FILES = {
    "conftest.py": f"""
        import importlib.util
        import sys
        sys.path.insert(0, {TESTS!r})
        spec = importlib.util.spec_from_file_location(
            "raytpu_guard", {os.path.join(TESTS, "conftest.py")!r})
        guard = sys.modules["raytpu_guard"] = importlib.util.module_from_spec(
            spec)
        spec.loader.exec_module(guard)
        from raytpu_guard import *  # noqa: F401,F403,E402  (hooks, fixtures)
        guard._PHASE_LIMIT_S["teardown"] = 2
        """,
    "pytest.ini": """
        [pytest]
        markers =
            timeout: limit of the call
        """,
    "test_call_hangs.py": """
        import threading
        import pytest

        def hold_for_ever(lock, parked):
            with lock:
                parked.set()
                threading.Event().wait(60)

        @pytest.mark.timeout(2)
        def test_blocks_on_a_held_lock():
            lock, parked = threading.Lock(), threading.Event()
            threading.Thread(target=hold_for_ever, args=(lock, parked),
                             name="the-holder", daemon=True).start()
            assert parked.wait(5)
            lock.acquire()
        """,
    "test_teardown_hangs.py": """
        import threading
        import pytest

        @pytest.fixture
        def ray_start_regular():   # stands in for the repo's: never boots
            yield
            threading.Event().wait(60)

        def test_first(ray_start_regular):
            pass

        def test_second(ray_start_regular):
            pass

        def test_own_init():
            import ray_tpu
            ray_tpu.init(num_cpus=1)   # never reached: the file asks for one

        def test_after_shutdown_is_cut_the_module_is_refused():
            pass
        """,
    "test_plain_after.py": """
        def test_no_runtime_asked():
            pass
        """,
    "test_mark_is_the_calls.py": """
        import time
        import pytest

        @pytest.fixture
        def slow_setup():
            time.sleep(4)

        @pytest.mark.timeout(2)
        def test_setup_outlasts_the_mark(slow_setup):
            pass
        """,
}


@pytest.fixture(scope="module")
def hung_run(tmp_path_factory):
    d = tmp_path_factory.mktemp("guard")
    for name, text in FILES.items():
        (d / name).write_text(textwrap.dedent(text))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", str(d), "-q", "-p",
         "no:cacheprovider", "-p", "xdist", "-n", "2", "--dist", "loadfile",
         "-p", "no:randomly", f"--junitxml={d / 'out.xml'}"],
        cwd=d, env=env, capture_output=True, text=True, timeout=120)
    cases = {}
    for c in ET.parse(d / "out.xml").getroot().iter("testcase"):
        said = "".join((e.get("message") or "") + (e.text or "")
                       for e in c if e.tag in ("failure", "error"))
        cases[c.get("name")] = (float(c.get("time")), said)
    return p, cases


def test_hung_call_fails_in_its_limit_with_every_threads_stack(hung_run):
    _p, cases = hung_run
    seconds, said = cases["test_blocks_on_a_held_lock"]
    assert 1.9 < seconds < 6.0, seconds
    assert "exceeded its 2s call limit" in said
    # the second thread by name, and where it sits
    assert "thread the-holder" in said and "hold_for_ever" in said
    assert "thread MainThread" in said and "lock.acquire()" in said


def test_cut_teardown_wedges_the_worker_not_the_run(hung_run):
    p, cases = hung_run
    seconds, said = cases["test_first"]
    assert 1.9 < seconds < 6.0, seconds      # teardown's limit, here 2 s
    assert "exceeded its 2s teardown limit" in said
    for later in ("test_second", "test_own_init",
                  "test_after_shutdown_is_cut_the_module_is_refused"):
        seconds, said = cases[later]
        assert seconds < 1.0, (later, seconds)
        assert ("runtime left wedged by test_teardown_hangs.py::test_first"
                in said), (later, said)
    # the run reached its end on live workers: exit 1, not 124
    assert p.returncode == 1, p.stdout[-2000:]
    assert "node down" not in p.stdout
    assert cases["test_no_runtime_asked"] == (pytest.approx(0, abs=1.0), "")


def test_mark_bounds_the_call_alone(hung_run):
    _p, cases = hung_run
    seconds, said = cases["test_setup_outlasts_the_mark"]
    assert said == "" and seconds >= 4.0, (seconds, said)
