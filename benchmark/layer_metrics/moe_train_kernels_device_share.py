"""Share of the chip's busy time in the traced span that the train step's
grouped expert products took (self time of ``moe_gmm``, ``moe_gmm_dx`` and
``moe_gmm_dw`` over the union of all operations): how much of the step the
routed experts are."""

from benchmark.layer_metrics._gdn import per
from benchmark.layer_metrics._moe_train import MOE_GMM_TRAIN, kernels_seconds


def read(ctx):
    return per(kernels_seconds(ctx, MOE_GMM_TRAIN), ctx["trace"]["busy_s"],
               100.0)
