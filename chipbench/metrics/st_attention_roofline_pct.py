"""st_attention_roofline_pct — token mixers: the attention kernel's share of its
roofline: the larger of (operations over the chip's bf16 peak) and (bytes over
its memory bandwidth) that the traced rounds' steps' attention needs
(`smallthinker_layers.attention_work`: from the pairs each kind's mask allows,
never from tiles visited; the forward counted twice, as the scopes' time holds
`remat`'s recomputation) over the device time of the scopes
`mixer/attention_full` and `mixer/attention_window` under `phase/train`."""
from chipbench import smallthinker_layers

LAYER = "token mixers"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return smallthinker_layers.attention_roofline_pct(ctx)
