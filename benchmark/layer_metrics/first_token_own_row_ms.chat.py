"""``first_token_own_row_ms`` (its file says what it reads) in a cell that does not judge
``ttft_p95_ms`` and so names another end-to-end metric that it moves:
admission and prefill stand between a stream's decode steps, so what makes
a first token later makes the time per token longer too."""
from benchmark.layer_metrics.first_token_own_row_ms import read  # noqa: F401
