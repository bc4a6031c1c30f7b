"""What the readers of the train step's kernels share (PR 39).  Not a
metric: no ``BENCHMARK.json`` entry names this file.

The kernels are rows of the trace summary's ``ops`` table under the names
the program pins (``ray_tpu/ops/moe.py`` ``KERNEL_MOE_GMM``, ``_DX``,
``_DW``; ``ray_tpu/ops/flash_attention.py``'s three; spelled out here, not
imported: these files also run over a parent commit whose program has no
such kernel in its train step, and give ``None`` there).  Their operations
and bytes are the block kind's (``ctx["model"].moe_gmm_train_flops /
_bytes``, ``mla_flash_train_flops / _bytes``), for one step's tokens on one
chip; a kind without the counts gives ``None``."""

from benchmark.layer_metrics._gdn import kernel_seconds

#: the grouped products of a train step: forward, the rows' gradient, the
#: weights' gradient
MOE_GMM_TRAIN = ("moe_gmm", "moe_gmm_dx", "moe_gmm_dw")
#: the attention kernels of a train step
FLASH_TRAIN = ("flash_fwd", "flash_dq", "flash_dkv")


def kernels_seconds(ctx: dict, kernels):
    """Self time of the named Pallas kernels in the traced span, summed;
    None when the trace holds none of them."""
    parts = [kernel_seconds(ctx, k) for k in kernels]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)


def step_roofline(ctx: dict, kernels, flops: float, nbytes: float):
    """100 x the least time the chip could take for one step's ``flops`` and
    ``nbytes`` (the larger of the two over their peaks) over the kernels'
    self time a step of the span."""
    seconds, steps = kernels_seconds(ctx, kernels), ctx["span"]["steps"]
    peaks = ctx["peaks"]
    if not (seconds and peaks and steps > 0):
        return None
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)


def step_shape(ctx: dict):
    """(sequences a chip a step, their length)."""
    tr = ctx["config"]["train"]
    return tr["global_batch"] / ctx["chips"], tr["sequence_length"]
