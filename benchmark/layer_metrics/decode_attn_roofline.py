"""Share of its roofline the decode-attention kernel reached in the traced
span.  The kernel is bound by memory: the least time is the live K/V bytes
of the span's decode steps over HBM bandwidth.  Live tokens a step are the
mean active slots (tokens out over steps) times the mean context of the
requests in flight (prompt plus half the output, as
``readers.decode_step_roofline`` takes it), their bytes the block kind's
own ``kv_bytes_per_token``; the decode steps are those the engine counted
between the span's two ``stats()`` (an admit counts one step, which is
taken off).  The kernel's name is the one ``ray_tpu/ops/decode_attention.py``
pins (spelled out here: this file also runs over a parent commit whose
program has no such kernel, and gives ``None`` there)."""

from benchmark.layer_metrics._counted import delta, per
from benchmark.layer_metrics._gdn import kernel_seconds
from benchmark.lib.readers import in_flight

DECODE_ATTN = "decode_attn"


def read(ctx):
    seconds = kernel_seconds(ctx, DECODE_ATTN)
    kv_bytes = getattr(ctx["model"], "kv_bytes_per_token", None)
    s0, s1 = ctx["span"]["stats0"], ctx["span"]["stats1"]
    steps, admits = delta(s0, s1, "steps"), delta(s0, s1, "admit_batches")
    live = in_flight(ctx)
    if not (seconds and kv_bytes and ctx["peaks"] and live and steps
            and admits is not None and steps - admits > 0):
        return None
    active = per(delta(s0, s1, "tokens_out"), steps)
    context = sum(s.prompt_len + len(s.token_times) / 2
                  for s in live) / len(live)
    least = ((steps - admits) * active * context * kv_bytes(ctx["config"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
