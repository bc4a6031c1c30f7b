"""Share of its roofline the one-token state-space decode kernel reached in
the traced span: the least time for one step at the span's mean active slots
(tokens out over steps; an idle slot's state is still read and written by
the kernel, which lowers the share), times the decode steps the engine
counted between the span's two ``stats()`` (an admit counts one step, which
is taken off), over the kernel's device seconds there."""

from benchmark.layer_metrics._ssd import (RECURRENT_STEP, per, roofline,
                                          span_delta)


def read(ctx):
    steps, admits = span_delta(ctx, "steps"), span_delta(ctx, "admit_batches")
    if steps is None or admits is None:
        return None
    active = per(span_delta(ctx, "tokens_out"), steps)
    if active is None or steps - admits <= 0:
        return None
    # the counts are linear in the slots: one step at ``active`` slots,
    # ``steps - admits`` times
    return roofline(ctx, RECURRENT_STEP, active * (steps - admits))
