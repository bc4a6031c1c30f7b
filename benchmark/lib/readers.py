"""Arithmetic that more than one per-layer reader shares.  A reader file
under ``layer_metrics/`` stays a few lines: it picks what to read out of the
traced run's context and calls into here."""

from __future__ import annotations

from typing import Optional

from . import trace

#: the engine's prefill programs are jitted from a function of this name
#: (``LLMEngine._prefill_fn``); everything else a serving replica runs in
#: the window is the decode program
PREFILL_PROGRAM = r"admit_fn"


def device_idle_share(ctx: dict) -> Optional[float]:
    tr = ctx["trace"]
    if not tr["window_s"] > 0 or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def span_delta(ctx: dict, key: str) -> float:
    return ctx["span"]["stats1"][key] - ctx["span"]["stats0"][key]


def prefill_seconds(ctx: dict) -> Optional[float]:
    """Device seconds of the prefill programs inside the traced span; None
    when batches were admitted there and no such program is in the trace
    (the name changed: better no number than a wrong one)."""
    seconds, count = trace.seconds_matching(ctx["trace"]["programs"],
                                            PREFILL_PROGRAM)
    if not count and span_delta(ctx, "admit_batches") > 0:
        return None
    return seconds


def in_flight(ctx: dict) -> list:
    """Requests whose life, send to end, overlaps the traced span."""
    t0, t1 = ctx["span"]["t0"], ctx["span"]["t1"]
    return [s for s in ctx["samples"]
            if s.token_times and s.t_fired < t1 and s.t_end > t0]


def decode_step_roofline(ctx: dict) -> Optional[float]:
    """The least time one decode step could take on this chip (the larger
    of its bytes over HBM bandwidth and its FLOPs over the bf16 peak) over
    the device time a decode step took in the traced span: the chip's busy
    time less the prefill programs', divided by the decode steps the engine
    counted between the span's two ends.  The context a step attends over
    is the mean over the requests in flight during the span of prompt plus
    half the output.  Bytes and FLOPs are the block kind's own
    (``ctx["model"]``, the file the configuration's ``model_type`` names)."""
    prefill = prefill_seconds(ctx)
    steps = span_delta(ctx, "steps") - span_delta(ctx, "admit_batches")
    if prefill is None or steps <= 0 or ctx["peaks"] is None:
        return None
    measured = (ctx["trace"]["busy_s"] - prefill) / steps
    live = in_flight(ctx)
    if not live or measured <= 0:
        return None
    context = sum(s.prompt_len + len(s.token_times) / 2 for s in live) \
        / len(live)
    active = span_delta(ctx, "tokens_out") / span_delta(ctx, "steps")
    doc, peaks, model = ctx["config"], ctx["peaks"], ctx["model"]
    least = max(
        model.decode_step_bytes(doc, active, active * context)
        / peaks["hbm_bytes_per_s"],
        model.decode_step_flops(doc, active, active * context)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / measured
