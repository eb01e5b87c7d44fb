"""sdar_masked_positions_pct — client step: of the positions the window's
block-diffusion steps normalised their loss over, the share the noise masked
(`positions_masked` over `positions_scored`, the program's counters on
`round/record`, sums over the window's rounds). Says the schedule engaged:
70 is the mean of U[0.45, 0.95]."""
from chipbench import sdar_layers

LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return sdar_layers.masked_positions_pct(ctx)
