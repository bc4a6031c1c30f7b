"""Of ``engine_first_token_ms``, the mean part of the admit's run on the chip
that was the request's own row: the run split by the positions the program
walked for that row over those of the whole admit
(``first_token_own_row_s`` / ``first_tokens``)."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "first_token_own_row_s"),
               delta(s0, s1, "first_tokens"), 1000.0)
