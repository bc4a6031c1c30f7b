"""The 95th percentile over the window's requests of the time to a first
token, from the scheduled arrival, on the callers' clock: the roll-up's
``ttft_p95_ms`` in a cell that does not judge it.  In ``serve-chat-steady``
the engine is never idle, so its passes keep one phase against the replayed
arrivals for a whole run; a request that reaches the queue within a
millisecond of the loop's look at it shifts that phase early in a run, and
with it nearly every first token after it: 251 and 260 of 280 requests came
55 and 35 ms later in the two runs of six that read 571 and 568 ms against
534-542 (PERF.md section 6, PR 34's first check), and a stop of the machine
inside the window multiplies the number.  No bound the contract allows
holds it there: reported, not judged; ``ttft_p50_ms.chat`` stands beside
it."""


def read(ctx):
    return ctx["roll"].get("ttft_p95_ms")
