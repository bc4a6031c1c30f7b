"""``prefill_ms_per_admitted_ktoken`` (its file says what it reads) in a
closed loop that is judged on ``latency_per_token_p95_ms`` alone: an admit
stands between a live stream's decode steps, so what a thousand admitted
tokens cost the chip is part of every answer's time per token, and in a queue
of rows of very uneven length it is a quarter of the chip."""
from benchmark.layer_metrics.prefill_ms_per_admitted_ktoken import (  # noqa: F401
    read)
