"""Padded rows among all rows the fixed-size prefill batches shipped over
the window.  ``stats()`` gives the cumulative fraction and the number of
batches; every batch has the same number of rows, which cancels."""


def read(ctx):
    s0, s1 = ctx["stats0"], ctx["stats1"]
    batches = s1["admit_batches"] - s0["admit_batches"]
    if batches <= 0:
        return None
    padded = (s1["padding_fraction"] * s1["admit_batches"]
              - s0["padding_fraction"] * s0["admit_batches"])
    return 100.0 * padded / batches
