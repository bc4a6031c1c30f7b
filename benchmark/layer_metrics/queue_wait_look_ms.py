"""Of ``engine_queue_wait_ms``, the mean wait over the window for the
engine's loop to look at its queue at all: ``submit`` to the first look, at
the top of a pass, that finds the request pending (``queue_look_s`` /
``admitted_requests``).  What a look between the steps of a dispatch would
take away."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "queue_look_s"),
               delta(s0, s1, "admitted_requests"), 1000.0)
