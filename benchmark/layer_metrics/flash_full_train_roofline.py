"""Share of their roofline the FULL attention layers' kernels of one train
step reached in the traced span, by name (``flash_fwd`` and the one backward
kernel ``flash_dkv``): the larger of FLOPs over the bf16 peak and bytes over
HBM bandwidth for one chip's sequences in the full layers, as the block kind
counts them (``flash_attention_flops / _bytes`` with the backward; a kind
whose count covers other layers too reads wrong here, so only a kind that
also counts its banded layers apart, ``flash_window_train_flops``, is
read), over the two kernels' self time a step."""

from benchmark.layer_metrics._moe_train import step_roofline, step_shape

FLASH_FULL_TRAIN = ("flash_fwd", "flash_dkv")


def read(ctx):
    model = ctx["model"]
    if not (hasattr(model, "flash_attention_flops")
            and hasattr(model, "flash_window_train_flops")):
        return None
    batch, seq = step_shape(ctx)
    doc = ctx["config"]
    return step_roofline(ctx, FLASH_FULL_TRAIN,
                         model.flash_attention_flops(doc, batch, seq, True),
                         model.flash_attention_bytes(doc, batch, seq, True))
