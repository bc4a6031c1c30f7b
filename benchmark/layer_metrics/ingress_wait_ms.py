"""Mean time over the window from a user request's arrival in the replica's
worker process to the engine's ``submit`` stamp: the actor's ordered queue,
the hand-over to the actor's loop and that loop's turn
(``ingress_queue_s``), then the replica's method up to the submit
(``ingress_submit_s``), over ``ingress_requests``."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "ingress_queue_s", "ingress_submit_s"),
               delta(s0, s1, "ingress_requests"), 1000.0)
