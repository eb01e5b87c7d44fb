"""st_expert_load_max_over_mean — expert layer: the most positions one held
expert was given in one step over the mean a held expert was given, from the
program's counters on `round/record`; mean over the window's rounds. 1.0 is
an even load."""
from chipbench import lfm2_layers

LAYER = "expert layer"
UNIT = "ratio"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.load_max_over_mean(ctx)
