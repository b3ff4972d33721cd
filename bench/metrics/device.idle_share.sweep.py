"""Share of the traced window in which no operation ran on the device
(1 - busy union / window, averaged over the chips)."""
from bench.trace import idle_share as read  # noqa: F401
