"""Share of the window the engine thread spent on its own work (building
and dispatching admits, dispatching decode, emitting and retiring) and not
blocked on the device or idle: ``loop_admit_s + loop_dispatch_s +
loop_emit_s`` over the time between the two snapshots (``t_mono``)."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    return per(delta(s0, s1, "loop_admit_s", "loop_dispatch_s",
                     "loop_emit_s"),
               delta(s0, s1, "t_mono"), 100.0)
