"""Share of its roofline the grouped expert matmul reached in the traced
span.  The kernel runs in both programs, and the two are bound differently,
so the least time is the sum of two: decode's, the assignments the engine
counted (``moe_assignments``: live tokens x experts a token x expert layers)
and the experts they touched (``moe_experts_touched``, summed over expert
layers and steps: each one's three matrices are read once), bound by memory;
and prefill's, the admitted prompt tokens' assignments
(``moe_assignments_prefill``) with every held expert of every expert layer
read once an admitted row (2,048 or more tokens reach them all), bound by
compute.  Each is the larger of its bytes over HBM bandwidth and its FLOPs
over the bf16 peak, as the block kind counts them (``moe_gmm_flops /
_bytes``), over the kernel's device seconds (self time of ``moe_gmm``, the
name ``ray_tpu/ops/moe.py`` pins; spelled out here: this file also runs over
a parent commit whose program has no such kernel, and gives ``None``
there).  Only assignments the kernel ran are counted: a padded position or
an idle slot is routed nowhere.  Counters are differenced at the span's two
``stats()``; the host counts a dispatch up to ``fetch_lag`` after the chip
ran it, so the two differ by up to one dispatch at each edge."""

from benchmark.layer_metrics._gdn import kernel_seconds, span_delta

MOE_GMM = "moe_gmm"


def least_seconds(ctx, assignments, experts_read):
    model, peaks, doc = ctx["model"], ctx["peaks"], ctx["config"]
    return max(model.moe_gmm_flops(doc, assignments)
               / peaks["bf16_flops_per_s"],
               model.moe_gmm_bytes(doc, assignments, experts_read)
               / peaks["hbm_bytes_per_s"])


def read(ctx):
    seconds = kernel_seconds(ctx, MOE_GMM)
    model = ctx["model"]
    counts = [span_delta(ctx, k) for k in (
        "moe_assignments", "moe_experts_touched", "moe_assignments_prefill",
        "admitted_requests")]
    if not (seconds and ctx["peaks"] and hasattr(model, "moe_gmm_flops")
            ) or None in counts:
        return None
    decode, touched, prefill, rows = counts
    stats = ctx["span"]["stats1"]
    held = stats["experts_held"] * stats["expert_layers"]
    least = (least_seconds(ctx, decode, touched)
             + least_seconds(ctx, prefill, rows * held))
    return 100.0 * least / seconds if least > 0 else None
