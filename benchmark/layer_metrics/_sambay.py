"""What the readers of SambaY's kernels and counters share (PR 60).  Not a
metric: no ``BENCHMARK.json`` entry names this file.

The kernels are rows of the trace summary's ``ops`` table under the names
``ray_tpu/ops/selective_scan.py`` pins (``KERNEL_CHUNK_FWD``,
``KERNEL_STEP``) and ``ray_tpu/ops/decode_attention.py``'s ring kernel's
(spelled out here, not imported: these files also run over a parent commit
whose program has none of them).  Seconds, the share of a roofline and the
counters' growth are ``_gdn.py``'s helpers: a trace without the kernel, a
kind without the counts or a span without the counter gives ``None``."""

from benchmark.layer_metrics._gdn import (kernel_seconds, per, roofline,
                                          span_delta)

CHUNK_FWD = "selective_scan_chunk_fwd"
STEP = "selective_scan_step"
WINDOW_DECODE_ATTN = "window_decode_attn"


def slot_steps(ctx):
    """(live slot, decode step) pairs of the traced span: the span's mean
    active slots (tokens out over steps) times the decode steps the engine
    counted between its two ``stats()`` (an admit counts one step, which is
    taken off); None where a counter is missing."""
    steps, admits = span_delta(ctx, "steps"), span_delta(ctx, "admit_batches")
    if steps is None or admits is None or steps - admits <= 0:
        return None
    active = per(span_delta(ctx, "tokens_out"), steps)
    return None if active is None else active * (steps - admits)


__all__ = ["CHUNK_FWD", "STEP", "WINDOW_DECODE_ATTN", "kernel_seconds",
           "roofline", "span_delta", "per", "slot_steps"]
