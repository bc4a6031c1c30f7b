"""Device milliseconds of one decode step in the traced span
(``_counted.decode_step_device_ms``), in a cell whose callers wait for
whole answers: it moves their latency per token."""
from benchmark.layer_metrics._counted import (  # noqa: F401
    decode_step_device_ms as read)
