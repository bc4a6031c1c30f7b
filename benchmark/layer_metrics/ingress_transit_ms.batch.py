"""Mean time over the window from the caller's stamp, taken in its process
before it chose a replica (``Router.start_stream``, so
``DeploymentHandle.stream``), to the call's arrival in the replica's worker
process: the route choice, the caller's flush window, outbox and pump, the
RPC (``ingress_transit_s`` / ``ingress_transit_n``; two processes of one
machine on the wall clock).  The native callers of the open loops send no
stamp and read nothing."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "ingress_transit_s"),
               delta(s0, s1, "ingress_transit_n"), 1000.0)
