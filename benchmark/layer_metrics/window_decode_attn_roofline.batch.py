"""Share of its roofline the ring's decode-attention kernel reached in the
traced span, in a cell that decodes one token a slot a step (the accepted
``window_decode_attn_roofline`` counts a speculative engine's rounds,
``spec_rounds``, which such an engine has none of): its work is the span's
(live slot, decode step) pairs, each one call's slot in every window layer,
one query over the window's positions of a ring.  Operations and bytes are
the block kind's (``window_decode_attn_flops / _bytes``: the positions the
query reads, not the whole ring the kernel fetches)."""

from benchmark.layer_metrics._sambay import (WINDOW_DECODE_ATTN, roofline,
                                             slot_steps)


def read(ctx):
    return roofline(ctx, WINDOW_DECODE_ATTN, slot_steps(ctx))
