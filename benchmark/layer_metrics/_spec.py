"""What the two readers of the speculative decode program share.  Not a
metric: no ``BENCHMARK.json`` entry names this file.

The program is a row of the trace summary's ``programs`` table under the
name ``ray_tpu/util/profiler.py`` pins (``jit_engine_spec_decode``; spelled
out here, not imported: these files also run over a parent commit whose
program has none).  A round is the two-token verify step of every live slot
and the drafting block's pass; the engine's ``steps`` counts a round a step
and an admit one more, which is taken off.  The host runs up to
``fetch_lag`` dispatches ahead of the chip, so the rounds counted and the
programs traced differ by up to one dispatch at each edge of the span."""

from benchmark.layer_metrics._counted import delta, per, program_seconds

#: ``jit_engine_spec_decode``: the rounds of one dispatch
SPEC_PROGRAM = "engine_spec_decode"


def rounds(ctx):
    """Rounds the engine dispatched between the span's two ``stats()``; None
    where the program does not speculate."""
    s0, s1 = ctx["span"]["stats0"], ctx["span"]["stats1"]
    steps, admits = delta(s0, s1, "steps"), delta(s0, s1, "admit_batches")
    if steps is None or admits is None or "spec_rounds" not in s1:
        return None
    return steps - admits


def round_seconds(ctx):
    """Device seconds of one round in the traced span."""
    return per(program_seconds(ctx, SPEC_PROGRAM), rounds(ctx))


__all__ = ["SPEC_PROGRAM", "rounds", "round_seconds", "delta", "per"]
