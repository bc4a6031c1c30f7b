"""Share of the traced span in which a chip ran a collective operation
(all-gather, reduce-scatter, all-reduce, ...) and nothing else."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_s"] > 0 or not tr["devices"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
