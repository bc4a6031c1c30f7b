"""Mean time over the window from a token's emit on the engine thread to
its yield on the replica's event loop, through the request's queue and
``LLMServer.__call__``'s executor thread (``deliver_lag_s`` /
``delivered_tokens``)."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "deliver_lag_s"),
               delta(s0, s1, "delivered_tokens"), 1000.0)
