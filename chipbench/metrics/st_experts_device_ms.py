"""st_experts_device_ms — expert layer: device time of the SmallThinker client step's
router (a top-6 of 64 logits read from the pre-attention norm, float32) and
the held ReGLU experts' grouped product (scopes `router` and `experts` under
`phase/train`, forward, recomputation and backward), per traced round."""
from chipbench import lfm2_layers

LAYER = "expert layer"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("router", "experts"))
