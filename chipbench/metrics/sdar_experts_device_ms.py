"""sdar_experts_device_ms — expert layer: device time of the block-diffusion
client step's softmax router (a top-8 of 128 in float32) and the held
experts' products over every position (scopes `router` and `experts` under
`phase/train`, forward, recomputation and backward; `lfm2_layers.py` would
count a conditional of the expert layer's here too: this model has none), per
traced round."""
from chipbench import lfm2_layers

LAYER = "expert layer"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("router", "experts"))
