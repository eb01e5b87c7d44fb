"""st_client_step_mfu_pct — client step: operations the traced rounds' steps
needed (forward + backward, counted on the plain reference over the pairs each
layer's mask allows, the experts' from the program's counter; recomputation
not counted) over their `phase/train` device time times the chip's bf16 peak:
a share of the whole step."""
from chipbench import smallthinker_layers

LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return smallthinker_layers.step_mfu_pct(ctx)
