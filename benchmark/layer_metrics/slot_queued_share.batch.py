"""Share of the traced span's slot-seconds in which a slot stood free while
the request that took it was already submitted: the later of the retire
before it and that ``submit``, to the dispatch of its admit (the loop's
look, a held bucket, the host's admit): ``slot_queued_s`` over
``num_slots`` x the span's ``t_mono`` (``_slots.py``)."""

from benchmark.layer_metrics._slots import share


def read(ctx):
    return share(ctx, "queued")
