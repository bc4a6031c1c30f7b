"""Share of the bytes a decode step has to move that are the recurrent
state's, read and written (``decode_state_bytes`` over
``decode_step_bytes``, the block kind's own counts), at the traced span's
mean active slots (tokens out over steps) and mean live positions a decode
step (``kv_positions_live``, the positions the active slots hold, not
rounded to the kernel's blocks, over the decode steps: an admit counts one
step, which is taken off), all from the growth of ``LLMServer.stats()``
between the span's two ends.  A kind without a per-slot state's count, or a
program without the counters, gives no number."""

from benchmark.layer_metrics._ssd import per, span_delta


def read(ctx):
    model = ctx["model"]
    state = getattr(model, "decode_state_bytes", None)
    steps, admits = span_delta(ctx, "steps"), span_delta(ctx, "admit_batches")
    if state is None or steps is None or admits is None:
        return None
    active = per(span_delta(ctx, "tokens_out"), steps)
    live = per(span_delta(ctx, "kv_positions_live"), steps - admits)
    if active is None or live is None:
        return None
    doc = ctx["config"]
    return per(state(doc, active),
               model.decode_step_bytes(doc, active, live), 100.0)
