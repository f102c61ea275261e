"""Share of the traced decode window in which no operation ran on the
device (%), averaged over the chips."""

from bench.metrics._shares import idle_share as read  # noqa: F401
