"""``decode_attn_roofline`` (its file says what it reads) in a closed loop
that is judged on ``latency_per_token_p95_ms`` alone: the K/V rows of the
softmax layers are one of the streams a decode step of such a cell waits
for, and a step is what an answer's time per token is made of."""
from benchmark.layer_metrics.decode_attn_roofline import read  # noqa: F401
