"""train_device_ms — client step: device time under the scope `phase/train`
(the clients' local SGD), per traced round."""
from chipbench import phases

LAYER = "client step"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.scope_device_ms(ctx, "phase/train")
