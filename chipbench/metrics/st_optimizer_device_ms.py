"""st_optimizer_device_ms — client step: device time of the torch-SGD update
over the whole state (scope `optimizer` under `phase/train`), per traced
round."""
from chipbench import lfm2_layers

LAYER = "client step"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("optimizer",))
