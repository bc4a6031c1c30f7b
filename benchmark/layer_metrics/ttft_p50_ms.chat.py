"""The median over the window's requests of the time to a first token (as
``ttft_p95_ms.chat``, the 50th percentile): 285.5-304.6 ms in 21 runs of
``serve-chat-steady`` whose 95th percentile read 533-571 (PERF.md section
6, PR 34), so it says whether first tokens as a whole came later when the
tail has changed its level."""


def read(ctx):
    return ctx["roll"].get("ttft_p50_ms")
