"""fused_update_device_ms — client step: device time of the operations named
`fused_sgd_update*` (the fused Pallas update, one kernel per VMEM chunk),
per traced round."""
from chipbench import phases

LAYER = "client step"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.scope_device_ms(ctx, phases.KERNEL)
