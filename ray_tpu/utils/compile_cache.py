"""Where JAX's persistent compile cache lives.

One rule for every script that starts a run on the chip (chip_smoke.py,
bench.py, bench_llm.py): where ``JAX_COMPILATION_CACHE_DIR`` is set, it is
used and no other directory is set in code; where it is not, the cache goes
to one fixed path inside the checkout.  The path is part of the cache's key,
so it is never made from a session directory, a pid, a time or a temporary
name.  JAX reads the variable itself, and workers inherit the environment
(core/node_agent.py), so calling ``place_compile_cache()`` before the first
process starts is all it takes.  Imports no JAX.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Export ``JAX_COMPILATION_CACHE_DIR`` (unless set) and return it."""
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(_CHECKOUT, ".jax_cache"))


def cache_entries(path: str) -> int:
    """Number of files under the cache directory (0 when it is not there)."""
    return sum(len(files) for _, _, files in os.walk(path))
