"""Share of the chip's busy time in the traced span that SambaY's kernels
took (self time of the two selective-scan kernels, the two decode-attention
kernels over rows and rings, and the two flash forwards of a prefill, over
the union of all operations), in a model whose every other operation is a
dense matmul, a norm or a convolution's few taps: whether the architecture's
distinctive kernels are a large share of the device's work."""

from benchmark.layer_metrics._sambay import (CHUNK_FWD, STEP,
                                             WINDOW_DECODE_ATTN,
                                             kernel_seconds, per)

KERNELS = (CHUNK_FWD, STEP, "decode_attn", WINDOW_DECODE_ATTN,
           "flash_window_prefill", "flash_fwd")


def read(ctx):
    parts = [kernel_seconds(ctx, k) for k in KERNELS]
    if all(p is None for p in parts):
        return None
    return per(sum(p or 0.0 for p in parts), ctx["trace"]["busy_s"], 100.0)
