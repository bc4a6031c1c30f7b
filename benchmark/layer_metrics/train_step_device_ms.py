"""Device milliseconds of one train-step program in the traced span, mean
over the chips: the host-clock ``train_step_ms`` less what the host adds
between programs."""

from benchmark.layer_metrics._counted import (TRAIN_PROGRAM, per,
                                              program_seconds)


def read(ctx):
    return per(program_seconds(ctx, TRAIN_PROGRAM), ctx["span"]["steps"],
               1000.0)
