"""Share of its roofline a verify-and-draft round reached in the traced
span: the least time the chip could take for one (the larger of its bytes
over HBM bandwidth and its FLOPs over the bf16 peak, as the block kind counts
them: ``spec_step_bytes / _flops``, weights read once, the experts the
round read, the rows and the rings' windows read) over the device time a
round took (``_spec.round_seconds``).  The experts read a layer and round
are the engine's own count (``moe_experts_touched`` over
``moe_expert_layer_steps``): greedy decoding over random weights sends a
step's tokens to fewer experts than uniform routing would, and weights that
were not read are not a round's least bytes.  The live slots a round are the
slot-rounds the engine counted (``spec_rounds``) over its rounds; the context
a slot attends over is the mean over the requests in flight during the span
of prompt plus half the output, as ``readers.decode_step_roofline`` takes it.
A kind without the counts, a program without the counter or a trace without
the program gives ``None``."""

from benchmark.layer_metrics._spec import delta, per, round_seconds, rounds
from benchmark.lib.readers import in_flight


def read(ctx):
    model, peaks, doc = ctx["model"], ctx["peaks"], ctx["config"]
    span = ctx["span"]
    slot_rounds = delta(span["stats0"], span["stats1"], "spec_rounds")
    measured, live = round_seconds(ctx), in_flight(ctx)
    if not (hasattr(model, "spec_step_bytes") and peaks and measured and live
            and slot_rounds):
        return None
    active = slot_rounds / rounds(ctx)
    read_a_layer = per(delta(span["stats0"], span["stats1"],
                             "moe_experts_touched"),
                       delta(span["stats0"], span["stats1"],
                             "moe_expert_layer_steps"))
    context = sum(s.prompt_len + len(s.token_times) / 2
                  for s in live) / len(live)
    least = max(
        model.spec_step_bytes(doc, active, active * context,
                              experts_read=read_a_layer)
        / peaks["hbm_bytes_per_s"],
        model.spec_step_flops(doc, active, active * context)
        / peaks["bf16_flops_per_s"])
    return per(100.0 * least, measured)
