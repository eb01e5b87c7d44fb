"""sdar_client_step_mfu_pct — client step: operations the traced rounds'
block-diffusion steps needed (forward + backward, counted on the plain
reference over the pairs the block mask allows, the experts' from the
program's counter) over their `phase/train` device time times the chip's bf16
peak: a share of the whole step."""
from chipbench import sdar_layers

LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return sdar_layers.step_mfu_pct(ctx)
