"""Share of the traced span in which the chip stood without a program while
a stream was live, as the engine's loop saw it at its dispatches
(``chip_unbound_s`` over the span's ``t_mono``): the loop's part of
``device_idle_share.serve``.  A stop of the whole machine shows in both
and in the line's ``host_stalls``."""

from benchmark.layer_metrics._counted import delta, per


def read(ctx):
    s0, s1 = ctx["span"]["stats0"], ctx["span"]["stats1"]
    return per(delta(s0, s1, "chip_unbound_s"), delta(s0, s1, "t_mono"),
               100.0)
