"""Share of the chip's busy time in the traced span that the two
gated-delta-rule kernels took (self time of ``gdn_chunk_fwd`` and
``gdn_recurrent_step`` over the union of all operations): whether the
architecture's distinctive part is a large share of the device's work."""

from benchmark.layer_metrics._gdn import (CHUNK_FWD, RECURRENT_STEP,
                                          kernel_seconds, per)


def read(ctx):
    parts = [kernel_seconds(ctx, k) for k in (CHUNK_FWD, RECURRENT_STEP)]
    if all(p is None for p in parts):
        return None
    return per(sum(p or 0.0 for p in parts), ctx["trace"]["busy_s"], 100.0)
