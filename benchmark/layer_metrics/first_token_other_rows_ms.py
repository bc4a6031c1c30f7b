"""Of ``engine_first_token_ms``, the mean part of the admit's run on the chip
that was the other rows of the same admit, walked one after another with
every first token given out when the last is done
(``first_token_other_rows_s`` / ``first_tokens``).  What is left of the
first-token wait after this, ``first_token_own_row_ms`` and
``first_token_ahead_ms`` is the fetch's return to the emit."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "first_token_other_rows_s"),
               delta(s0, s1, "first_tokens"), 1000.0)
