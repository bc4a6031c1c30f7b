"""Mean time over the window from the engine's ``_retire`` of a request to
the reply that carries the end of its stream leaving the replica
(``next_chunks`` returning ``done``): ``finish_deliver_s`` /
``finished_streams``.  What stands between a finished answer and a closed
loop's caller learning of it, which is when it sends the next."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "finish_deliver_s"),
               delta(s0, s1, "finished_streams"), 1000.0)
