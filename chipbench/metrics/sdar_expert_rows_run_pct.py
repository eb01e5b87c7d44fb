"""sdar_expert_rows_run_pct — expert layer: of the (position, held expert)
rows that every held expert over every position would multiply, the share
the expert layers' products multiplied (`expert_rows_run` over
`expert_rows_all`, the program's counters on `round/record`, sums over the
window's rounds; the first counts the tiles the grouped product visited,
their padding and a tile two experts share once for each). Says the grouped
product engaged: 100 where every held expert runs over every position (any
CPU run); nothing from a program without the counts, or where no row was
counted."""
from chipbench import lfm2_layers

LAYER = "expert layer"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    counts = lfm2_layers.window_counts(ctx, lfm2_layers.RECORD_SPAN)
    keys = ("expert_rows_run", "expert_rows_all")
    if not counts or any(k not in c for c in counts for k in keys):
        return None
    every = sum(c["expert_rows_all"] for c in counts)
    return (100.0 * sum(c["expert_rows_run"] for c in counts) / every
            if every else None)
