"""Share of the traced span in which no operation ran, mean over chips."""
from benchmark.lib.readers import device_idle_share as read  # noqa: F401
