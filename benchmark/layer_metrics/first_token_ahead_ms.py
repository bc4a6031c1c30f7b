"""Of ``engine_first_token_ms``, the mean time over the window from the
dispatch of a request's admit to the admit's start on the chip: the programs
the host had in flight ahead of it (``first_token_ahead_s`` /
``first_tokens``).  The start is the later of the dispatch and the end of the
program before it, as the engine thread's blocking fetches return."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "first_token_ahead_s"),
               delta(s0, s1, "first_tokens"), 1000.0)
