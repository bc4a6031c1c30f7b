"""Device milliseconds of one verify-and-draft round in the traced span
(``_spec.round_seconds``: the speculative decode programs' device time over
the rounds the engine counted).  With a draft accepted a round yields two
tokens a slot, so this is a time per round, not per token
(``mtp_accept_share`` says how many rounds yield two)."""

from benchmark.layer_metrics._spec import (SPEC_PROGRAM,  # noqa: F401
                                           per, round_seconds)


def read(ctx):
    return per(round_seconds(ctx), 1.0, 1000.0)
