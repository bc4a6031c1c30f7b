"""Share of the drafted tokens the model accepted over the window:
``spec_accepted`` over ``spec_drafted``, both cumulative keys of
``LLMServer.stats()`` (a round of a live slot drafts one token with the
model's own multi-token-prediction block; it is accepted where it is the
model's own greedy choice).  Over random weights it reads chance, about one
in the vocabulary: the cell pays the draft and the two-token verify and keeps
one token a round, and what a trained checkpoint would accept is not known
here.  A program without the counters gives ``None``."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "spec_accepted"), delta(s0, s1, "spec_drafted"),
               100.0)
