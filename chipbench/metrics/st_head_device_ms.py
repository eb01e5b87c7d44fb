"""st_head_device_ms — client step: device time of the final norm and the untied
head's product over a row's 8,192 positions and the held 18,992 rows of the
vocabulary (scope `head` under `phase/train`, forward and backward), per
traced round."""
from chipbench import lfm2_layers

LAYER = "client step"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("head",))
