"""From request samples to the end-to-end metrics of one window.  The
percentile rule and the per-request TPOT are ``bench_llm.request_rollup``'s
(sound arithmetic, copied: the yardstick may not move with the program)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence


def pct(xs: Sequence[float], q: float) -> Optional[float]:
    """The q-quantile by rank: ``sorted(xs)[min(n - 1, int(n * q))]``."""
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else None


def serve_window(samples, unfinished, window_s: float,
                 deadline_s: float) -> Dict[str, object]:
    """Roll up one serving window ``[0, window_s)``.

    ``samples`` are finished requests (``loadgen.Sample``), ``unfinished``
    the ``(planned, t_start)`` pairs still in flight at ``deadline_s`` (the
    window's end plus the drain grace).  A request is *attempted* if its
    start (scheduled arrival, or send in a closed loop) lies in the window;
    it *failed* if it raised, returned fewer tokens than asked for, or was
    not complete at the deadline.  TTFT is first token time - start; TPOT
    of a request with at least two tokens is (last token time - first token
    time) / (tokens - 1), per request and not per gap because the engine
    delivers tokens in groups; ``latency_per_token`` is (last token time -
    start) / tokens, what a caller who waits for the whole answer pays per
    token (printed, not judged).  Tokens are counted where they arrived:
    the rate is over every token received inside the window, from requests
    of the pre-roll too, divided by the whole window."""
    in_win = [s for s in samples if 0.0 <= s.t_start < window_s]
    late = [s for s in in_win if s.complete and s.t_end > deadline_s]
    failed = [s for s in in_win if not s.complete] + late
    n_unfinished = sum(1 for _r, t in unfinished if 0.0 <= t < window_s)
    good = [s for s in in_win if s.complete and s.t_end <= deadline_s]
    ttft = [s.token_times[0] - s.t_start for s in good]
    tpot = [(s.token_times[-1] - s.token_times[0]) / (len(s.token_times) - 1)
            for s in good if len(s.token_times) >= 2]
    per_token = [(s.token_times[-1] - s.t_start) / len(s.token_times)
                 for s in good]
    tokens_in = sum(1 for s in samples for t in s.token_times
                    if 0.0 <= t < window_s)
    lateness = [s.t_fired - s.t_start for s in in_win]

    def ms(v):
        return None if v is None else v * 1000.0

    return {
        "attempted": len(in_win) + n_unfinished,
        "failed": len(failed) + n_unfinished,
        "errors": sorted({s.error for s in failed if s.error})[:5],
        "completed": len(good),
        "backlog_at_end": n_unfinished + sum(
            1 for s in in_win if s.t_end > window_s),
        "tokens_in_window": tokens_in,
        "serve_out_tokens_per_s": tokens_in / window_s,
        "requests_per_s": len(good) / window_s,
        "ttft_p50_ms": ms(pct(ttft, 0.50)),
        "ttft_p95_ms": ms(pct(ttft, 0.95)),
        "tpot_p50_ms": ms(pct(tpot, 0.50)),
        "tpot_p95_ms": ms(pct(tpot, 0.95)),
        "latency_per_token_p50_ms": ms(pct(per_token, 0.50)),
        "latency_per_token_p95_ms": ms(pct(per_token, 0.95)),
        "latency_p50_ms": ms(pct([s.t_end - s.t_start for s in good], 0.5)),
        "n_ttft": len(ttft), "n_tpot": len(tpot),
        "lateness_p95_ms": ms(pct(lateness, 0.95)),
        "prompt_tokens": sum(s.prompt_len for s in good),
    }


def share_meeting(samples, window_s: float, ttft_limit_s: float,
                  tpot_limit_s: float) -> Optional[float]:
    """Share of attempted requests meeting both limits; a failed one misses."""
    in_win = [s for s in samples if 0.0 <= s.t_start < window_s]
    if not in_win:
        return None
    ok = 0
    for s in in_win:
        if not s.complete:
            continue
        n = len(s.token_times)
        tpot = ((s.token_times[-1] - s.token_times[0]) / (n - 1)
                if n >= 2 else 0.0)
        ok += (s.token_times[0] - s.t_start <= ttft_limit_s
               and tpot <= tpot_limit_s)
    return ok / len(in_win)


def train_window(step_ends: List[float], losses: List[float],
                 tokens_per_step: int, chips: int) -> Dict[str, object]:
    """Roll up one training window: ``step_ends[i]`` is when step i's loss
    had been read, relative to the window's first instant.  The runner
    starts a step while less than ``--seconds`` have passed and runs the
    last one to its end, so the window is every step it started, over the
    time from the first instant to the end of the last: all the work and
    all the time it took, a stall after any step included.  (Cut at
    ``--seconds`` exactly a run reads 88 or 89 steps of 563 ms: a 1.1% jump
    that is no change in speed.)"""
    import math
    durs = [b - a for a, b in zip([0.0] + step_ends[:-1], step_ends)]
    return {
        "steps": len(step_ends),
        "window_s": step_ends[-1] if step_ends else 0.0,
        "train_tokens_per_s_per_chip":
            (len(step_ends) * tokens_per_step / step_ends[-1] / chips)
            if step_ends else 0.0,
        "step_ms_median": (pct(durs, 0.5) or 0.0) * 1000.0,
        "step_ms_p95": (pct(durs, 0.95) or 0.0) * 1000.0,
        "nonfinite": sum(1 for x in losses if not math.isfinite(x)),
    }
