"""Device milliseconds of the prefill programs in the traced span per
thousand real prompt tokens prefilled in it.  The engine counts admitted
rows, not their tokens, so the tokens are those of the requests whose first
token reached its caller inside the span: the first token comes out of the
prefill program itself.  That holds only where tokens stream; where they
arrive when the request ends (``readers.streamed``) the two sets are
different requests and the reader returns nothing."""

from benchmark.lib.readers import prefill_seconds, streamed


def read(ctx):
    prefill = prefill_seconds(ctx)
    if prefill is None or not streamed(ctx["samples"]):
        return None
    t0, t1 = ctx["span"]["t0"], ctx["span"]["t1"]
    tokens = sum(s.prompt_len for s in ctx["samples"]
                 if s.token_times and t0 <= s.token_times[0] < t1)
    if tokens <= 0:
        return None
    return prefill * 1000.0 / (tokens / 1000.0)
