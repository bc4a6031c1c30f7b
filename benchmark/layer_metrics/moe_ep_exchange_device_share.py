"""Share of the chip's busy time in the traced span that the core stood in
the collectives of the experts' exchange over ``ep``: self time on the
operations line of ``collective-permute`` (the synchronous form, and the
``-start`` / ``-done`` of the asynchronous one, whose wait is the time the
core stands in it), mean over the chips, over the union of all operations.
The exchange is told from the step's other collectives by kind: the dense
leaves' are all-gathers and all-reduces, and nothing else in the step
permutes.  A kind that does not count an exchange
(``moe_ep_exchange_bytes``), or a trace without the kind of collective,
gives nothing."""

from benchmark.layer_metrics._counted import per
from benchmark.lib import trace

EXCHANGE = r"^collective-permute(-start|-done)?$"


def read(ctx):
    if not hasattr(ctx["model"], "moe_ep_exchange_bytes"):
        return None
    seconds, count = trace.seconds_matching(ctx["trace"]["ops"], EXCHANGE)
    return per(seconds, ctx["trace"]["busy_s"], 100.0) if count else None
