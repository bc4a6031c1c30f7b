"""The load's imbalance between the chips that share the experts, as the
device shows it: the largest less the smallest ``collective_exposed_s``
among the chips of the traced span, over the span.  A chip whose experts
got fewer assignments is done sooner and waits longer in the next
collective for the others; the slowest of them sets the step's pace.  (The
step's own counters, ``moe_chip_load_max`` / ``_min``, stay in its
``metrics``: the train runner hands a reader no step metrics.)  Read only
for a kind that counts an exchange (``moe_ep_exchange_bytes``)."""


def read(ctx):
    tr = ctx["trace"]
    if not hasattr(ctx["model"], "moe_ep_exchange_bytes") \
            or len(tr["devices"]) < 2 or not tr["window_s"] > 0:
        return None
    waits = [d["collective_exposed_s"] for d in tr["devices"]]
    return 100.0 * (max(waits) - min(waits)) / tr["window_s"]
