"""Share of the chip's busy time in the traced span that the window
layers' two kernels, the rows' decode-attention kernel and the grouped
expert matmul took (self time of ``window_decode_attn``,
``flash_window_prefill``, ``decode_attn`` and ``moe_gmm`` over the union of
all operations): whether the architecture's distinctive kernels are a large
share of the device's work."""

from benchmark.layer_metrics._gdn import kernel_seconds, per

KERNELS = ("window_decode_attn", "flash_window_prefill", "decode_attn",
           "moe_gmm")


def read(ctx):
    parts = [kernel_seconds(ctx, k) for k in KERNELS]
    if all(p is None for p in parts):
        return None
    return per(sum(p or 0.0 for p in parts), ctx["trace"]["busy_s"], 100.0)
