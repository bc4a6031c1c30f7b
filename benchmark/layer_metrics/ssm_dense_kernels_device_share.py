"""Share of the chip's busy time in the traced span that the two state-space
kernels and the two attention kernels took (self time of ``ssd_chunk_fwd``,
``ssd_recurrent_step``, ``decode_attn`` and ``flash_fwd`` over the union of
all operations), in a model whose every other operation is a dense matmul,
a norm or a convolution's few taps: whether the architecture's distinctive
kernels are a large share of the device's work."""

from benchmark.layer_metrics._ssd import (CHUNK_FWD, RECURRENT_STEP,
                                          kernel_seconds, per)

KERNELS = (CHUNK_FWD, RECURRENT_STEP, "decode_attn", "flash_fwd")


def read(ctx):
    parts = [kernel_seconds(ctx, k) for k in KERNELS]
    if all(p is None for p in parts):
        return None
    return per(sum(p or 0.0 for p in parts), ctx["trace"]["busy_s"], 100.0)
