"""Share of the traced span's slot-seconds in which a slot stood free and no
request had asked for it: from the retire of the request that left it to
the ``submit`` of the one that took it (``slot_unfed_s`` over ``num_slots``
x the span's ``t_mono``, ``_slots.py``).  What a closed loop's callers,
the way in and the end of the stream together leave empty.  Says the whole
account on an information line as it reads."""

from benchmark.layer_metrics._slots import say_account, share


def read(ctx):
    say_account(ctx)
    return share(ctx, "unfed")
