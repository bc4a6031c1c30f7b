"""Share of their roofline the attention kernels of one train step reached
in the traced span, by name (``flash_fwd``, ``flash_dq``, ``flash_dkv``):
the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth for the
useful work at the published head sizes, as the block kind counts it
(``mla_flash_train_flops / _bytes``: a program that pads heads of 192 / 128
to 256 multiplies more, which shows here as a lower share), over the three
kernels' self time a step."""

from benchmark.layer_metrics._moe_train import (FLASH_TRAIN, step_roofline,
                                                step_shape)


def read(ctx):
    model = ctx["model"]
    if not hasattr(model, "mla_flash_train_flops"):
        return None
    batch, seq = step_shape(ctx)
    doc = ctx["config"]
    return step_roofline(ctx, FLASH_TRAIN,
                         model.mla_flash_train_flops(doc, batch, seq),
                         model.mla_flash_train_bytes(doc, batch, seq))
