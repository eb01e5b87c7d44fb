"""st_mixer_device_ms — token mixers: device time of the SmallThinker client step's
token mixers (scope `mixer` under `phase/train`: the projections, RoPE in the
window layers, the transposes, the attention kernel's calls of both kinds and
`o_proj`; forward, recomputation and backward), per traced round."""
from chipbench import lfm2_layers

LAYER = "token mixers"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("mixer",))
