"""Share of its roofline a decode step reached in the traced span
(``readers.decode_step_roofline``), in a cell whose callers read tokens as
they are made: it moves the time per output token."""
from benchmark.lib.readers import decode_step_roofline as read  # noqa: F401
