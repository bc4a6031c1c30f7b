"""Share of their roofline the grouped expert products of one train step
reached in the traced span: the larger of FLOPs over the bf16 peak and bytes
over HBM bandwidth, as the block kind counts them for one step's tokens at
the expected assignments (``moe_gmm_train_flops / _bytes``: forward, the
rows' gradient, the weights' gradient, and the forward again only where the
configuration's ``remat`` replays it), over the self time a step of
``moe_gmm``, ``moe_gmm_dx`` and ``moe_gmm_dw``.  Routing is data: a step
whose batch lands more assignments on the held experts than the expected
three quarters of a token's reads higher."""

from benchmark.layer_metrics._moe_train import (MOE_GMM_TRAIN, step_roofline,
                                                step_shape)


def read(ctx):
    model = ctx["model"]
    if not hasattr(model, "moe_gmm_train_flops"):
        return None
    batch, seq = step_shape(ctx)
    doc = ctx["config"]
    return step_roofline(ctx, MOE_GMM_TRAIN,
                         model.moe_gmm_train_flops(doc, batch * seq),
                         model.moe_gmm_train_bytes(doc, batch * seq))
