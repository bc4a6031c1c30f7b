"""``ingress_wait_ms`` (its file says what it reads) in a cell that does not
judge ``ttft_p95_ms`` and so names another end-to-end metric that it
moves: the replica's loop that takes a request in is the loop that hands
every token out."""
from benchmark.layer_metrics.ingress_wait_ms import read  # noqa: F401
