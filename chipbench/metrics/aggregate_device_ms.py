"""aggregate_device_ms — server aggregation: device time under the scope
`phase/aggregate` (fault and screen pass, `aggregate_fn`), per traced round."""
from chipbench import phases

LAYER = "server aggregation"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.scope_device_ms(ctx, "phase/aggregate")
