"""st_experts_roofline_pct — expert layer: the grouped expert product's share of
its roofline: the larger of (operations over the chip's bf16 peak) and (bytes
over its memory bandwidth) that the traced rounds' routed (position, held
expert) pairs need (`smallthinker_layers.experts_work`: from the program's
`expert_tokens_held`, never from rows padded; the forward counted twice, as
the scope's time holds `remat`'s recomputation) over the device time of the
scope `experts` under `phase/train`."""
from chipbench import smallthinker_layers

LAYER = "expert layer"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return smallthinker_layers.experts_roofline_pct(ctx)
