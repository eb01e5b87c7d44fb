"""idle_attributed_pct — device: share of device 0's idle time in the traced
span whose gap midpoint lies inside a leaf span of the program
(`round/plan`, `/stage`, `/enqueue`, `/fetch`, `/record`,
`round/checkpoint`), read from the same trace."""
from chipbench import phases

LAYER = "device"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    reduced = phases.run_phases(ctx)
    return reduced["idle_attributed_pct"] if reduced else None
