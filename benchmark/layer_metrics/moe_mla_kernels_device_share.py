"""Share of the chip's busy time in the traced span that the grouped expert
matmul and the latent decode-attention kernel took (self time of ``moe_gmm``
and ``mla_decode_attn`` over the union of all operations): whether the
architecture's distinctive kernels are a large share of the device's
work."""

from benchmark.layer_metrics._gdn import kernel_seconds, per

KERNELS = ("moe_gmm", "mla_decode_attn")


def read(ctx):
    parts = [kernel_seconds(ctx, k) for k in KERNELS]
    if all(p is None for p in parts):
        return None
    return per(sum(p or 0.0 for p in parts), ctx["trace"]["busy_s"], 100.0)
