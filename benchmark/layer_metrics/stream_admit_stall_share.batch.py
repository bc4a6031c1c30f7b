"""``stream_admit_stall_share`` (its file says what it reads) in the closed
loops, whose callers wait for whole answers and so judge
``latency_per_token_p95_ms``: an admit holds every answer under way."""
from benchmark.layer_metrics.stream_admit_stall_share import read  # noqa: F401
