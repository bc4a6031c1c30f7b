"""Share of the KV cache's positions that decode attention read over the
window: ``kv_positions_read`` (per decode step, the live positions of the
active slots rounded up to the kernel's blocks) over ``kv_positions_held``
(steps x slots x ``max_len``), both cumulative in ``LLMServer.stats()``.  A
program that reads every slot's whole row whatever its length has neither
counter: no number."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "kv_positions_read"),
               delta(s0, s1, "kv_positions_held"), 100.0)
