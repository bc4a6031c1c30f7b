"""The share of a live stream's time over the window in which the chip ran
somebody's prefill: every program's run times the requests that had a first
token and were not retired at its dispatch, the admits' against all
(``stream_admit_s`` / ``stream_s``).  What chunked prefill between decode
dispatches has to move."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "stream_admit_s"),
               delta(s0, s1, "stream_s"), 100.0)
