"""global_battery_device_ms — evaluation batteries: device time under the scope
`phase/global_battery` (the new global model on the clean, poisoned and
per-trigger test sets), per traced round."""
from chipbench import phases

LAYER = "evaluation batteries"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.scope_device_ms(ctx, "phase/global_battery")
