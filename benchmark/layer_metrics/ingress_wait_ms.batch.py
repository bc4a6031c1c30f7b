"""``ingress_wait_ms`` (its file says what it reads) in the closed loops,
whose callers wait for whole answers and so judge
``latency_per_token_p95_ms``: the way in is part of every answer's time."""
from benchmark.layer_metrics.ingress_wait_ms import read  # noqa: F401
