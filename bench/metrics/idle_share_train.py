"""Share of the traced training window in which no operation ran on the
device (%), averaged over the chips."""

from bench.metrics._shares import idle_share as read  # noqa: F401
