"""Share of the bytes a decode step has to move that are the ONE full
layer's rows, read once by that layer and once by every cross layer
(``decode_shared_kv_bytes`` over ``decode_step_bytes``, the block kind's own
counts), at the traced span's mean active slots (tokens out over steps) and
the shared rows the engine counted read (``shared_kv_positions_read``: live
positions x the layers that read them, over the decode steps: an admit
counts one step, which is taken off; the kind's count of readers turns it
back into live positions), all from the growth of ``LLMServer.stats()``
between the span's two ends.  A kind without the count, or a program without
the counter, gives no number."""

from benchmark.layer_metrics._sambay import per, span_delta


def read(ctx):
    model, doc = ctx["model"], ctx["config"]
    shared = getattr(model, "decode_shared_kv_bytes", None)
    steps, admits = span_delta(ctx, "steps"), span_delta(ctx, "admit_batches")
    read_ = span_delta(ctx, "shared_kv_positions_read")
    if shared is None or steps is None or admits is None or read_ is None:
        return None
    active = per(span_delta(ctx, "tokens_out"), steps)
    readers = per(model.kv_bytes_per_token(doc),
                  model.kv_bytes_held_per_token(doc))
    live = per(read_, (steps - admits) * readers if readers else None)
    if active is None or live is None:
        return None
    return per(shared(doc, live), model.decode_step_bytes(doc, active, live),
               100.0)
