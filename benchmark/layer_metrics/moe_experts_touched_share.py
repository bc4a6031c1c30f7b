"""Share of an expert layer's held experts that a decode step touched, over
the window: ``moe_experts_touched`` (experts with at least one live token,
summed over expert layers and steps) over ``moe_expert_layer_steps`` (steps
that found a live slot, times the expert layers) times the experts a layer
holds (``experts_held``), all from ``LLMServer.stats()``.  It is how much of
the expert weights a step has to read: 64 (1 - (1 - 4/64)^n) of 64 under
uniform routing of n live tokens.  A program without dropless experts has
none of the counters: no number."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    steps = delta(s0, s1, "moe_expert_layer_steps")
    held = s1.get("experts_held")
    if steps is None or not held:
        return None
    return per(delta(s0, s1, "moe_experts_touched"), steps * held, 100.0)
