"""The whole train step's share of the chip's bf16 peak (MFU): the FLOPs of
one step's tokens on one chip, as the configuration's block kind counts them
(``ctx["model"].train_flops_per_token``: forward and backward, attention
included), over the peak times the device time of one ``train_step``
program in the traced span.  It bounds what ``flash_attn_roofline`` can
claim: a kernel taken off the path leaves its roofline silent, the step's
share stays."""

from benchmark.layer_metrics._counted import (TRAIN_PROGRAM, per,
                                              program_seconds)


def read(ctx):
    step_s = per(program_seconds(ctx, TRAIN_PROGRAM), ctx["span"]["steps"])
    if step_s is None or ctx["peaks"] is None:
        return None
    doc, tr = ctx["config"], ctx["config"]["train"]
    seq = tr["sequence_length"]
    flops = ctx["model"].train_flops_per_token(doc, seq) \
        * tr["global_batch"] * seq / ctx["chips"]
    return 100.0 * flops / (ctx["peaks"]["bf16_flops_per_s"] * step_s)
