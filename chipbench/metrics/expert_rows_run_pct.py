"""expert_rows_run_pct — expert layer: of the (position, held expert) rows
that every held expert over every position would multiply, the share the
expert layers' grouped product multiplied (`expert_rows_run` over
`expert_rows_all`, the program's counters on `round/record`, sums over the
window's rounds; tile padding and a tile two experts share counted, a visit
of one of an expert's two width blocks as half its rows). Says the grouped
product engaged: 100 where every held expert runs over every position (any
CPU run); nothing from a program without the counts (the parent, whose
layer gathered into buffers)."""
from chipbench import lfm2_layers, smallthinker_layers

LAYER = "expert layer"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return smallthinker_layers.window_share_pct(
        ctx, lfm2_layers.RECORD_SPAN, "expert_rows_run", "expert_rows_all")
