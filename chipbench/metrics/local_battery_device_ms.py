"""local_battery_device_ms — evaluation batteries: device time under the scope
`phase/local_battery` (every client's model on the clean and poisoned test
sets), per traced round."""
from chipbench import phases

LAYER = "evaluation batteries"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.scope_device_ms(ctx, "phase/local_battery")
