"""sdar_expert_load_max_over_mean — expert layer: the most positions one held
expert was given in one block-diffusion step over the mean a held expert was
given, from the program's counters on `round/record`; mean over the window's
rounds. 1.0 is an even load; a step's MASK positions are one token and route
alike, so a held expert that MASK chooses reads many times the mean (which is
why this model gathers no buffers: `models/sdar.py`)."""
from chipbench import lfm2_layers

LAYER = "expert layer"
UNIT = "ratio"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.load_max_over_mean(ctx)
