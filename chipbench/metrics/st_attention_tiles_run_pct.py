"""st_attention_tiles_run_pct — token mixers: of the (query tile, key tile) pairs
a full mask would give the attention kernel's calls of one forward pass over
a row, the share the causal mask and the window leave it to visit
(`attention_tiles_run` over `attention_tiles_all`, the program's static counts
on `round/plan`, sums over the window's rounds). Says the kernel engaged and
skipped tiles; nothing where both are 0 (XLA's form runs) or where the program
counts neither."""
from chipbench import lfm2_layers, smallthinker_layers

LAYER = "token mixers"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return smallthinker_layers.window_share_pct(
        ctx, lfm2_layers.PLAN_SPAN, "attention_tiles_run",
        "attention_tiles_all")
