"""Share of its roofline a decode step reached in the traced span
(``readers.decode_step_roofline``), in a cell whose callers wait for whole
answers: it moves their latency per token."""
from benchmark.lib.readers import decode_step_roofline as read  # noqa: F401
