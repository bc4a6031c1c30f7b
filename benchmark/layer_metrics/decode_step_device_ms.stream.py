"""Device milliseconds of one decode step in the traced span
(``_counted.decode_step_device_ms``), in a cell whose callers read tokens
as they are made: it moves the time per output token."""
from benchmark.layer_metrics._counted import (  # noqa: F401
    decode_step_device_ms as read)
