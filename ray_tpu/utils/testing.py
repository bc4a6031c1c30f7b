"""Test helpers: virtual CPU device meshes (SURVEY §4 takeaway — a fake mesh/ICI
backend so multi-host pjit code paths run in CI without TPUs)."""

from __future__ import annotations

import os


def force_cpu_devices(n: int = 8) -> None:
    """Force jax onto `n` virtual CPU devices for this process.

    Must run before the first jax backend use.  Sets both the env (for
    child processes) and jax.config (for this one, should jax already have
    read another value from the environment, e.g. ``JAX_PLATFORMS=tpu,cpu``
    on a host with a chip).
    """
    flag = f"--xla_force_host_platform_device_count={n}"
    xf = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xf:
        os.environ["XLA_FLAGS"] = (xf + " " + flag).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


#: Environment for subprocess workers that should see the virtual CPU mesh.
CPU_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}
