"""Share of its roofline the one-token selective-scan decode kernel reached
in the traced span: the least time for the span's (live slot, decode step)
pairs (an idle slot's state is still read and written by the kernel, which
lowers the share) over the kernel's device seconds there."""

from benchmark.layer_metrics._sambay import STEP, roofline, slot_steps


def read(ctx):
    return roofline(ctx, STEP, slot_steps(ctx))
