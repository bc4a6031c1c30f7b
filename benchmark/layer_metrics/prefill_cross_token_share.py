"""Prompt tokens taken through the cross-decoder over those taken through
the self-decoder, in percent (``prefill_cross_tokens`` over
``prefill_self_tokens``, both cumulative in ``LLMServer.stats()``): a
prefill that walks the cross-decoder over a prompt's last token alone reads
one over the mean prompt; one that walked it over the whole prompt would read
100.  A program without the counters: no number."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "prefill_cross_tokens"),
               delta(s0, s1, "prefill_self_tokens"), 100.0)
