"""pip/venv runtime-env isolation: two tasks with conflicting package
versions run side by side on one cluster (reference:
``python/ray/_private/runtime_env/pip.py`` + per-node ``uri_cache.py``)."""

import os
import zipfile

import pytest

import ray_tpu
from ray_tpu.utils.testing import CPU_WORKER_ENV


def _build_wheel(dist_dir: str, name: str, version: str, body: str) -> str:
    """Hand-roll a minimal wheel (a zip with code + dist-info) — no network,
    no build backend needed."""
    tag = f"{name}-{version}"
    path = os.path.join(dist_dir, f"{name}-{version}-py3-none-any.whl")
    meta = (f"Metadata-Version: 2.1\nName: {name}\nVersion: {version}\n")
    wheel_meta = ("Wheel-Version: 1.0\nGenerator: test\nRoot-Is-Purelib: "
                  "true\nTag: py3-none-any\n")
    with zipfile.ZipFile(path, "w") as z:
        z.writestr(f"{name}/__init__.py", body)
        z.writestr(f"{tag}.dist-info/METADATA", meta)
        z.writestr(f"{tag}.dist-info/WHEEL", wheel_meta)
        record = (f"{name}/__init__.py,,\n{tag}.dist-info/METADATA,,\n"
                  f"{tag}.dist-info/WHEEL,,\n{tag}.dist-info/RECORD,,\n")
        z.writestr(f"{tag}.dist-info/RECORD", record)
    return path


@pytest.mark.timeout(180)
def test_pip_env_failure_fails_task(tmp_path):
    """A pip env that cannot be built must FAIL the task with the real error
    (reference: RuntimeEnvSetupError) — not hang ray.get while the agent
    retries pip forever."""
    ray_tpu.init(num_cpus=2, worker_env=dict(CPU_WORKER_ENV))
    try:
        @ray_tpu.remote(runtime_env={
            "pip": ["definitely-not-a-package-xyz==9.9"],
            "pip_args": ["--no-index", "--find-links", str(tmp_path)]})
        def doomed():
            return 1

        with pytest.raises(Exception) as ei:
            ray_tpu.get(doomed.remote(), timeout=120)
        assert "pip install failed" in str(ei.value) or \
            "RuntimeEnvSetupError" in type(ei.value).__name__
    finally:
        ray_tpu.shutdown()


@pytest.mark.timeout(120)
def test_conflicting_pip_envs_one_cluster(tmp_path):
    wheels = str(tmp_path)
    _build_wheel(wheels, "confl", "1.0", "VERSION = '1.0'\n")
    _build_wheel(wheels, "confl", "2.0", "VERSION = '2.0'\n")
    pip_args = ["--no-index", "--find-links", wheels]

    ray_tpu.init(num_cpus=4, worker_env=dict(CPU_WORKER_ENV))
    try:
        @ray_tpu.remote(runtime_env={"pip": ["confl==1.0"],
                                     "pip_args": pip_args})
        def v1():
            import confl
            return confl.VERSION

        @ray_tpu.remote(runtime_env={"pip": ["confl==2.0"],
                                     "pip_args": pip_args})
        def v2():
            import confl
            return confl.VERSION

        @ray_tpu.remote
        def plain():
            import importlib.util
            return importlib.util.find_spec("confl") is None

        r1, r2 = v1.remote(), v2.remote()
        assert ray_tpu.get([r1, r2], timeout=300) == ["1.0", "2.0"]
        # the default interpreter never sees either install
        assert ray_tpu.get(plain.remote(), timeout=60) is True
        # venv workers are cached per env hash: a second call reuses the env
        assert ray_tpu.get(v1.remote(), timeout=120) == "1.0"
    finally:
        ray_tpu.shutdown()
