"""Share of its roofline the chunked state-space prefill kernel reached in
the traced span: the least time for the prompt tokens the engine admitted
between the span's two ``stats()`` (``admit_tokens_real``: padding of the
bucket is work the kernel does and nobody needs, so it lowers the share)
over the kernel's device seconds there.  The host dispatches an admit up to
a second before the chip runs it, so the tokens counted and the kernels
traced differ by up to one admit at each edge of the span."""

from benchmark.layer_metrics._ssd import CHUNK_FWD, roofline, span_delta


def read(ctx):
    return roofline(ctx, CHUNK_FWD, span_delta(ctx, "admit_tokens_real"))
