"""Median time of a training step in the window, host clock, each step
ended by a blocking read of its loss."""


def read(ctx):
    return ctx["roll"]["step_ms_median"] or None
