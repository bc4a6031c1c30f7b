"""Share of the chip's busy time in the traced span that the train step's
attention kernels took: the full layers' flash kernels and the band's two
(self time of each by its pinned name, over the union of all operations).
The grouped expert products are ``moe_train_kernels_device_share``'s, which
this kind's cell reports beside it; what the two leave is the compiler's
own: the dense projections, the head and the loss, the sort and the
combine, the optimizer, the collectives."""

from benchmark.layer_metrics._gdn import per
from benchmark.layer_metrics._moe_train import kernels_seconds

SWA_ATTN_TRAIN = ("flash_fwd", "flash_dkv", "flash_window_prefill",
                  "flash_window_bwd")


def read(ctx):
    if kernels_seconds(ctx, ("flash_window_bwd",)) is None:
        return None
    return per(kernels_seconds(ctx, SWA_ATTN_TRAIN), ctx["trace"]["busy_s"],
               100.0)
