"""``admit_padding_token_share`` (its file says what it reads) in a closed
loop that is judged on tokens/s: every position an admit walks that holds no
token is chip time taken from the live streams' decode steps, and in a queue
of long prompts whose buckets double it was a third of the positions walked."""
from benchmark.layer_metrics.admit_padding_token_share import read  # noqa: F401
