"""sdar_mixer_device_ms — token mixers: device time of the block-diffusion
client step's attention over both streams (scope `mixer` under `phase/train`,
forward, recomputation and backward), per traced round."""
from chipbench import lfm2_layers

LAYER = "token mixers"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("mixer",))
