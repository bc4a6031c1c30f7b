"""Share of their roofline the banded attention kernels of one train step
reached in the traced span, by name (the band's forward
``flash_window_prefill`` and its backward ``flash_window_bwd``): the larger
of FLOPs over the bf16 peak and bytes over HBM bandwidth for the USEFUL work
of a band of ``sliding_window`` in the sliding layers, as the block kind
counts it (``flash_window_train_flops / _bytes``: the keys a query reads,
not the blocks a kernel walks, so what a kernel computes beyond the band
shows as a lower share), over the two kernels' self time a step."""

from benchmark.layer_metrics._moe_train import step_roofline, step_shape

FLASH_WINDOW_TRAIN = ("flash_window_prefill", "flash_window_bwd")


def read(ctx):
    model = ctx["model"]
    if not hasattr(model, "flash_window_train_flops"):
        return None
    batch, seq = step_shape(ctx)
    doc = ctx["config"]
    return step_roofline(ctx, FLASH_WINDOW_TRAIN,
                         model.flash_window_train_flops(doc, batch, seq),
                         model.flash_window_train_bytes(doc, batch, seq))
