"""``decode_slot_occupancy`` (its file says what it reads) in a closed loop
that is judged on ``latency_per_token_p95_ms`` alone: a step costs the
state and the weights of its live slots, so how full the steps ran is what
the time per token was paid for."""
from benchmark.layer_metrics.decode_slot_occupancy import read  # noqa: F401
