"""experts_device_ms — expert layer: device time of the client step's router
and feed-forward products, the held experts' and the dense layer's (scopes
`router` and `experts` under `phase/train`, forward and backward, and the
expert layer's conditionals, which the trace leaves without a scope path:
`lfm2_layers.py`), per traced round."""
from chipbench import lfm2_layers

LAYER = "expert layer"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("router", "experts"))
