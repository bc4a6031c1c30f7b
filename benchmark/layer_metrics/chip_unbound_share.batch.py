"""``chip_unbound_share`` (its file says what it reads) in the closed loops
that are not judged on tokens/s: a chip left standing holds every answer
under way, so it moves ``latency_per_token_p95_ms``."""
from benchmark.layer_metrics.chip_unbound_share import read  # noqa: F401
