"""sdar_noise_device_ms — client step: device time of a block-diffusion
step's noise (scope `noise` under `phase/train`: a masking rate a block and a
draw a position from the step's key, the noisy and the clean stream built),
per traced round."""
from chipbench import lfm2_layers

LAYER = "client step"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("noise",))
