"""Device milliseconds of the prefill programs in the traced span per
thousand prompt tokens the engine admitted between the span's two
``stats()`` (``admit_tokens_real``: counted at the admit, so the metric
reads wherever prompts are admitted, streamed to their callers or not).
The host dispatches an admit up to a second before the chip runs it, so
the admits counted and the programs traced differ by up to one at each edge
of the span, and one admit of eight can carry a quarter of a span's tokens
(PERF.md, PR 25)."""

from benchmark.layer_metrics._counted import delta, per
from benchmark.lib.readers import prefill_seconds


def read(ctx):
    span = ctx["span"]
    tokens = delta(span["stats0"], span["stats1"], "admit_tokens_real")
    return per(prefill_seconds(ctx), per(tokens, 1000.0), 1000.0)
