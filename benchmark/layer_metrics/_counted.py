"""What the readers of the program's own counters and program names share
(PR 25).  Not a metric: no ``BENCHMARK.json`` entry names this file.

The counters are cumulative keys of ``LLMServer.stats()``; a reader
differences two snapshots.  The programs are rows of the trace summary's
``programs`` table, found by the names ``ray_tpu/util/profiler.py`` pins
(spelled out here, not imported: these files also run over a parent commit
whose program has neither).  Whatever is missing, a key or a name, gives
``None``: a line without the metric, never a wrong number."""

from benchmark.lib import trace

#: ``jit_engine_decode``: ``steps_per_dispatch`` decode steps, dense or paged
DECODE_PROGRAM = r"engine_decode"
#: ``jit_train_step``: forward, backward and optimizer update
TRAIN_PROGRAM = r"train_step"


def delta(s0: dict, s1: dict, *keys: str):
    """Sum over ``keys`` of their growth from ``s0`` to ``s1``."""
    if any(k not in s0 or k not in s1 for k in keys):
        return None
    return sum(s1[k] - s0[k] for k in keys)


def per(num, den, scale: float = 1.0):
    """``scale * num / den`` where both were read and ``den`` is positive."""
    if num is None or den is None or den <= 0:
        return None
    return scale * num / den


def program_seconds(ctx: dict, pattern: str):
    """Device seconds of the programs named ``pattern`` in the traced span
    (mean over the chips); None when the trace holds none."""
    seconds, count = trace.seconds_matching(ctx["trace"]["programs"], pattern)
    return seconds if count else None


def decode_step_device_ms(ctx: dict):
    """Device milliseconds of the decode programs in the traced span per
    decode step the engine counted between the span's two ``stats()`` (an
    admit counts one step, which is taken off).  The host runs up to
    ``fetch_lag`` dispatches ahead of the chip, so the dispatches counted
    and the programs traced differ by up to one at each edge of the span:
    right on average, one dispatch in ten off in a run (PERF.md, PR 25)."""
    span = ctx["span"]
    steps = delta(span["stats0"], span["stats1"], "steps")
    admits = delta(span["stats0"], span["stats1"], "admit_batches")
    if steps is None or admits is None:
        return None
    return per(program_seconds(ctx, DECODE_PROGRAM), steps - admits, 1000.0)
