"""Mean wait of a request in the engine's pending queue over the window:
``submit`` to the dispatch of the admit that carries it, counted by the
engine thread (``queue_wait_s`` / ``admitted_requests``)."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "queue_wait_s"),
               delta(s0, s1, "admitted_requests"), 1000.0)
