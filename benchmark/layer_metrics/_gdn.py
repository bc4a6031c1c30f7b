"""What the readers of the two gated-delta-rule kernels share.  Not a
metric: no ``BENCHMARK.json`` entry names this file.

The kernels are rows of the trace summary's ``ops`` table under the names
``ray_tpu/ops/gated_delta.py`` pins (``KERNEL_CHUNK_FWD``,
``KERNEL_RECURRENT_STEP``; spelled out here, not imported: these files also
run over a parent commit whose program has neither) with the tag
``lib/trace`` puts on a Pallas kernel.  Their operations and bytes are the
block kind's (``ctx["model"].<kernel>_flops / _bytes``).  A trace without
the kernel, a kind without the counts or a span without the counter gives
``None``: a line without the metric, never a wrong number."""

import re

from benchmark.layer_metrics._counted import delta, per
from benchmark.lib import trace

CHUNK_FWD = "gdn_chunk_fwd"
RECURRENT_STEP = "gdn_recurrent_step"


def kernel_seconds(ctx: dict, kernel: str):
    """Device seconds (self time) of the Pallas kernel ``kernel`` in the
    traced span; None when the trace holds none."""
    seconds, count = trace.seconds_matching(
        ctx["trace"]["ops"], "^" + re.escape(kernel + trace.PALLAS_TAG) + "$")
    return seconds if count else None


def roofline(ctx: dict, kernel: str, work: float):
    """100 x the least time the chip could take for ``work`` units of the
    kernel (the larger of its FLOPs over the bf16 peak and its bytes over
    HBM bandwidth, as the kind counts them) over the kernel's seconds."""
    model, peaks = ctx["model"], ctx["peaks"]
    flops = getattr(model, kernel + "_flops", None)
    nbytes = getattr(model, kernel + "_bytes", None)
    seconds = kernel_seconds(ctx, kernel)
    if not (flops and nbytes and peaks and seconds and work and work > 0):
        return None
    doc = ctx["config"]
    least = max(flops(doc, work) / peaks["bf16_flops_per_s"],
                nbytes(doc, work) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def span_delta(ctx: dict, *keys: str):
    return delta(ctx["span"]["stats0"], ctx["span"]["stats1"], *keys)


__all__ = ["CHUNK_FWD", "RECURRENT_STEP", "kernel_seconds", "roofline",
           "span_delta", "per"]
