"""Share of its roofline the chunked selective-scan prefill kernel reached in
the traced span: the least time for the prompt tokens the engine admitted
between the span's two ``stats()`` (``admit_tokens_real``: padding of the
bucket is work the kernel skips a chunk at a time and nobody needs) over the
kernel's device seconds there.  The recurrence has no matrix form, so the
kernel is the vector unit's and the share of a roofline drawn from the
matrix unit's peak and the HBM's bandwidth stays low by nature.  The host
dispatches an admit up to a second before the chip runs it, so the tokens
counted and the kernels traced differ by up to one admit at each edge of the
span."""

from benchmark.layer_metrics._sambay import CHUNK_FWD, roofline, span_delta


def read(ctx):
    return roofline(ctx, CHUNK_FWD, span_delta(ctx, "admit_tokens_real"))
