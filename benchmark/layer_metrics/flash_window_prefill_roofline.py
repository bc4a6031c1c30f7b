"""Share of its roofline the banded flash forward reached in the traced
span.  Its work is the positions the admits of the span walked (prompt tokens
and the padding to their buckets, ``admit_tokens_real`` +
``admit_tokens_padded``), of the rows that took the kernel: a row under 1,024
positions takes plain attention, so the positions are scaled by the kernel's
calls in the trace over the window layers' calls the admitted rows would
make.  Operations and bytes are the block kind's at the configuration's
largest bucket, a position's share of them (the band makes both linear in the
row: ``flash_window_prefill_flops / _bytes``, counted by the blocks the band
needs).  The kernel's name is the one ``ray_tpu/ops/flash_attention.py`` pins
(spelled out here: this file also runs over a parent commit whose program has
no such kernel, and gives ``None`` there)."""

import re

from benchmark.layer_metrics._counted import delta
from benchmark.lib import trace

FLASH_WINDOW = "flash_window_prefill"


def read(ctx):
    model, peaks, doc = ctx["model"], ctx["peaks"], ctx["config"]
    seconds, calls = trace.seconds_matching(
        ctx["trace"]["ops"],
        "^" + re.escape(FLASH_WINDOW + trace.PALLAS_TAG) + "$")
    s0, s1 = ctx["span"]["stats0"], ctx["span"]["stats1"]
    walked = delta(s0, s1, "admit_tokens_real", "admit_tokens_padded")
    rows = delta(s0, s1, "admitted_requests")
    flops = getattr(model, "flash_window_prefill_flops", None)
    if not (flops and peaks and seconds and calls and walked and rows):
        return None
    layers = s1.get("window_layers")
    if not layers:
        return None
    bucket = max(doc["serve"]["buckets"])
    positions = walked * min(1.0, calls / (rows * layers))
    least = max(flops(doc, 1, bucket) / peaks["bf16_flops_per_s"],
                model.flash_window_prefill_bytes(doc, 1, bucket)
                / peaks["hbm_bytes_per_s"]) * positions / bucket
    return 100.0 * least / seconds
