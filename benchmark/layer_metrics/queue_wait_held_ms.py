"""Of ``engine_queue_wait_ms``, the mean time over the window that a request
spent seen and left behind: from the first look that found it pending to the
look that admitted it, because the admit took another bucket, was full, or no
slot was free (``queue_held_s`` / ``admitted_requests``).  What is left of
the queue wait after this and ``queue_wait_look_ms`` is the host building and
dispatching the admit."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "queue_held_s"),
               delta(s0, s1, "admitted_requests"), 1000.0)
