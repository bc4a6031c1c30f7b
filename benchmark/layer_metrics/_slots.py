"""What the readers of a slot's time by state share (PR 56).  Not a metric:
no ``BENCHMARK.json`` entry names this file.

``LLMEngine`` books every slot-second into one of five states (taken with
no first token yet, decoding, ended on the chip with the host yet to know,
free with the request that will take it already submitted, free with
nobody asking) and ``counters()`` closes the open intervals at ``t_mono``,
so between ANY two snapshots the five ``slot_*_s`` grow by ``num_slots`` x
the time between them.  That is why these read the traced span's own pair
(``ctx["span"]``, taken inside the window) and not the window's, which in
a traced closed loop holds the drain.  A key the program lacks (a parent
commit's) gives ``None``: a line without the metric."""

from benchmark.layer_metrics._counted import delta, per
from benchmark.runners.common import compact, say

STATES = ("prefill", "live", "tail", "queued", "unfed")


def slot_seconds(ctx: dict):
    """Slot-seconds of the traced span: ``num_slots`` x its ``t_mono``."""
    s0, s1 = ctx["span"]["stats0"], ctx["span"]["stats1"]
    seconds = delta(s0, s1, "t_mono")
    if seconds is None or "num_slots" not in s1:
        return None
    return s1["num_slots"] * seconds


def share(ctx: dict, state: str):
    """Percent of the span's slot-seconds that ``state`` took."""
    span = ctx["span"]
    return per(delta(span["stats0"], span["stats1"], f"slot_{state}_s"),
               slot_seconds(ctx), 100.0)


def say_account(ctx: dict):
    """The five shares and their sum on one information line (the
    live share has no metric of its own: it is what the others leave), and
    beside them, per request admitted in the span, the slot's wait for its
    request against the request's wait for anything."""
    s0, s1 = ctx["span"]["stats0"], ctx["span"]["stats1"]
    shares = {state: share(ctx, state) for state in STATES}
    if None in shares.values():
        return
    admitted = delta(s0, s1, "admitted_requests")
    say("slot account over the traced span: " + compact({
        **shares, "sum": sum(shares.values()),
        "slot_queued_ms_per_admit": per(
            delta(s0, s1, "slot_queued_s"), admitted, 1000.0),
        "queue_wait_ms_per_admit": per(
            delta(s0, s1, "queue_wait_s"), admitted, 1000.0),
        "retired": delta(s0, s1, "retired_requests")}, 6))
