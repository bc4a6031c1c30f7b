"""Share of its roofline the latent decode-attention kernel reached in the
traced span: the least time for the live latent rows of the span's decode
steps, the larger of their bytes over HBM bandwidth and the score and value
FLOPs over the bf16 peak (``mla_decode_attn_flops / _bytes`` of the block
kind, at the published 576 numbers a token a layer), over the kernel's
device seconds.  The live rows are the engine's own count
(``kv_positions_live``: at every decode step, the positions each active slot
holds with its new token, as they are, not rounded up to the kernel's
blocks), differenced at the span's two ``stats()``; the host counts a
dispatch up to ``fetch_lag`` after the chip ran it, so the two differ by up
to one dispatch at each edge.  The kernel's name is the one
``ray_tpu/ops/decode_attention.py`` pins (spelled out here: this file also
runs over a parent commit whose program has neither the kernel nor the
counter, and gives ``None`` there)."""

from benchmark.layer_metrics._gdn import kernel_seconds, span_delta

MLA_DECODE_ATTN = "mla_decode_attn"


def read(ctx):
    seconds = kernel_seconds(ctx, MLA_DECODE_ATTN)
    model, peaks = ctx["model"], ctx["peaks"]
    tokens = span_delta(ctx, "kv_positions_live")
    if not (seconds and peaks and tokens
            and hasattr(model, "mla_decode_attn_flops")):
        return None
    doc = ctx["config"]
    least = max(model.mla_decode_attn_flops(doc, tokens)
                / peaks["bf16_flops_per_s"],
                model.mla_decode_attn_bytes(doc, tokens)
                / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
