"""Mean time a token over the window from its emit on the engine thread to
the return of ``req.out.get`` on the executor thread of
``LLMServer.__call__`` (the queue, that thread's wake-up):
``deliver_thread_s`` / ``delivered_tokens``.  With
``stream_deliver_loop_ms`` it adds up to ``stream_deliver_lag_ms``."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "deliver_thread_s"),
               delta(s0, s1, "delivered_tokens"), 1000.0)
