"""bench.py without a TPU: a measurement script that finds no chip FAILS —
exit code non-zero and a message naming what it found — instead of printing
a "skipped" line and exiting 0, which a driver reads as a pass.  Runs
bench.py in a subprocess, against a stub ``jax`` whose ``devices()`` raises
the way a TPU backend that cannot initialise does, and against the real jax
held to the CPU."""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _write_stub_jax(tmp_path, raise_src: str):
    pkg = tmp_path / "jax"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(textwrap.dedent(f"""
        class errors:
            class JaxRuntimeError(RuntimeError):
                pass

        def devices():
            {raise_src}
    """))


def _run_bench(*args, env):
    return subprocess.run(
        [sys.executable, str(REPO / "bench.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
        env={"PATH": "/usr/bin:/bin", "HOME": "/tmp", **env})


@pytest.mark.parametrize("raise_src", [
    # plain RuntimeError from xla_bridge
    "raise RuntimeError(\"Unable to initialize backend 'tpu': "
    "UNAVAILABLE: TPU backend setup/compile error (Unavailable).\")",
    # the chained original: the plugin's JaxRuntimeError
    "raise errors.JaxRuntimeError(\"UNAVAILABLE: TPU backend setup/compile "
    "error (Unavailable).\")",
])
def test_bench_backend_init_failure_fails_and_says_why(tmp_path, raise_src):
    _write_stub_jax(tmp_path, raise_src)
    proc = _run_bench(env={"PYTHONPATH": str(tmp_path)})
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "no TPU" in proc.stderr and "UNAVAILABLE" in proc.stderr, \
        proc.stderr[-2000:]
    assert proc.stdout.strip() == "", "a failed run prints no result line"


def test_bench_chipspeed_fails_without_a_backend(tmp_path):
    """``--chipspeed`` fails exactly like the headline path: non-zero, the
    reason on stderr, no result line and no partial checkpoint."""
    _write_stub_jax(tmp_path, "raise RuntimeError(\"Unable to initialize "
                              "backend 'tpu': UNAVAILABLE\")")
    proc = _run_bench("--chipspeed", env={"PYTHONPATH": str(tmp_path)})
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "no TPU" in proc.stderr and "UNAVAILABLE" in proc.stderr, \
        proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert not (REPO / "BENCH_CHIPSPEED_partial.json").exists()


def test_bench_on_cpu_devices_fails_naming_the_device_found():
    """The real jax, held to the CPU: the backend comes up, so the script
    must look at what it got.  CPU devices are not a TPU: non-zero, and the
    message names the platform, kind and count it found and the explicit
    rehearsal switches."""
    proc = _run_bench(env={"JAX_PLATFORMS": "cpu",
                           "PYTHONPATH": os.pathsep.join(sys.path)})
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]
    assert "platform=cpu" in proc.stderr and "kind=cpu" in proc.stderr
    assert "--preset debug" in proc.stderr and "--allow-cpu" in proc.stderr
    assert proc.stdout.strip() == ""


# ------------------------------------------------------ the compile cache

def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(
        monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: used as it is, nothing else set in
    code.  Unset: one fixed path inside the checkout, the same on every
    call (no pid, time or temporary name in it)."""
    from ray_tpu.utils.compile_cache import cache_entries, place_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert place_compile_cache() == "/some/dir"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert place_compile_cache() == str(REPO / ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(REPO / ".jax_cache")
    assert place_compile_cache() == str(REPO / ".jax_cache")
    assert cache_entries("/no/such/dir") == 0
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")


def test_workers_inherit_the_compile_cache_dir(monkeypatch, tmp_path):
    """Set before the session starts, every worker process sees the same
    directory (core/node_agent.py copies the environment), and its jax
    reads it from there."""
    import ray_tpu
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    ray_tpu.init(num_cpus=2, worker_env=dict(CPU_WORKER_ENV))
    try:
        @ray_tpu.remote
        def where():
            import jax
            return (os.environ.get("JAX_COMPILATION_CACHE_DIR"),
                    jax.config.jax_compilation_cache_dir)

        assert ray_tpu.get(where.remote(), timeout=120) == (
            str(tmp_path), str(tmp_path))
    finally:
        ray_tpu.shutdown()
