"""Share of its roofline the ring's decode-attention kernel reached in the
traced span.  Its work is the (live slot, round) pairs the engine counted
between the span's two ``stats()`` (``spec_rounds``): each is one call's
slot in every window layer, two queries over the window's positions of a
ring.  Operations and bytes are the block kind's
(``window_decode_attn_flops / _bytes``: the positions the queries read, not
the whole ring the kernel fetches); the kernel's name is the one
``ray_tpu/ops/decode_attention.py`` pins (spelled out here: this file also
runs over a parent commit whose program has no such kernel, and gives
``None`` there)."""

from benchmark.layer_metrics._gdn import roofline, span_delta

WINDOW_DECODE_ATTN = "window_decode_attn"


def read(ctx):
    return roofline(ctx, WINDOW_DECODE_ATTN, span_delta(ctx, "spec_rounds"))
