"""Padding among all positions of the ``[prefill_batch, bucket]`` arrays the
engine prefilled over the window: padded rows and the tail of every real
row up to its bucket (``admit_tokens_padded`` against it plus
``admit_tokens_real``)."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "admit_tokens_padded"),
               delta(s0, s1, "admit_tokens_padded", "admit_tokens_real"),
               100.0)
