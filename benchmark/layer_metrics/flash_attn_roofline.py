"""The least time the chip could take for the attention kernels of one
training step (forward, dq, dkv: the larger of FLOPs over the bf16 peak and
bytes over HBM bandwidth, as the configuration's block kind counts them:
``ctx["model"].flash_attention_flops / _bytes``) over the device time of
the Pallas attention custom calls per step in the traced span."""

import re

from benchmark.lib import trace

#: every Pallas kernel of the train step is one of ops/flash_attention.py's
#: three (forward, dq, dkv); the trace names them by the enclosing JAX
#: primitive (``closed_call``, ``checkpoint``, ``shard_map``), so the reader
#: goes by the tag ``lib/trace`` puts on a ``tpu_custom_call``
KERNELS = re.escape(trace.PALLAS_TAG) + "$"


def read(ctx):
    seconds, count = trace.seconds_matching(ctx["trace"]["ops"], KERNELS)
    steps = ctx["span"]["steps"]
    model = ctx["model"]
    if not count or steps <= 0 or ctx["peaks"] is None \
            or not hasattr(model, "flash_attention_flops"):
        return None
    doc, peaks, tr = ctx["config"], ctx["peaks"], ctx["config"]["train"]
    batch = tr["global_batch"] / ctx["chips"]
    seq = tr["sequence_length"]
    least = max(
        model.flash_attention_flops(doc, batch, seq, backward=True)
        / peaks["bf16_flops_per_s"],
        model.flash_attention_bytes(doc, batch, seq, backward=True)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
