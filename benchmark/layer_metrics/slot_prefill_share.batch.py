"""Share of the traced span's slot-seconds in which a slot was taken and had
no first token yet: the dispatch of its admit to the request's first
``_emit`` (the programs ahead, the admit's own run, the fetch):
``slot_prefill_s`` over ``num_slots`` x the span's ``t_mono``
(``_slots.py``)."""

from benchmark.layer_metrics._slots import share


def read(ctx):
    return share(ctx, "prefill")
