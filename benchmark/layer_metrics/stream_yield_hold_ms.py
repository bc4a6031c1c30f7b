"""Mean time a token over the window from the ``yield`` in
``LLMServer.__call__`` to the generator being resumed: what the actor's
streaming reply, its put and its back-pressure cost a token
(``yield_hold_s`` / ``delivered_tokens``)."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "yield_hold_s"),
               delta(s0, s1, "delivered_tokens"), 1000.0)
