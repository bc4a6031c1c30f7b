"""Mean time over the window a buffered stream's first chunk lay in the
replica's buffer before a poll took it (``first_chunk_wait_s`` /
``first_chunks``): milliseconds where the caller's polls reach the replica
as the tokens are made, about an answer's length where they wait behind
the request in the caller's pump."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "first_chunk_wait_s"),
               delta(s0, s1, "first_chunks"), 1000.0)
