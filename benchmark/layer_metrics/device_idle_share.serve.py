"""Share of the traced span in which no operation ran on the chip."""
from benchmark.lib.readers import device_idle_share as read  # noqa: F401
