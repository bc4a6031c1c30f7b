"""Mean time over the window from the dispatch of a request's admit to its
first token on its queue: the prefill program, whatever decode dispatch was
in flight before it, and the engine's fetch lag (``first_token_wait_s`` /
``first_tokens``)."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "first_token_wait_s"),
               delta(s0, s1, "first_tokens"), 1000.0)
