"""Share of the traced span's slot-seconds in which a slot's request had
ended on the chip and the host did not know yet: the step that made its
last token to ``_retire`` (the rest of the dispatch, the fetch, the emit
loop): ``slot_tail_s`` over ``num_slots`` x the span's ``t_mono``
(``_slots.py``)."""

from benchmark.layer_metrics._slots import share


def read(ctx):
    return share(ctx, "tail")
