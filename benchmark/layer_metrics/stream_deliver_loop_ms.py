"""Mean time a token over the window from the return of ``req.out.get`` on
the executor thread of ``LLMServer.__call__`` to the replica's loop
resuming the generator (the hand-over, the loop's turn):
``deliver_loop_s`` / ``delivered_tokens``.  With
``stream_deliver_thread_ms`` it adds up to ``stream_deliver_lag_ms``."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "deliver_loop_s"),
               delta(s0, s1, "delivered_tokens"), 1000.0)
