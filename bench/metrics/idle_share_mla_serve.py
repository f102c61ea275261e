"""Share of the traced decode window of the latent attention and expert
cell in which no operation ran on the device (%)."""

from bench.metrics._shares import idle_share as read  # noqa: F401
