"""st_attention_window_device_ms — token mixers: device time of the attention
kernel's calls in the window layers (causal mask with a 4,096-key window,
RoPE; scope `mixer/attention_window` under `phase/train`: the forward kernel,
its recomputation and the backward kernel), per traced round. Nothing from a
program without the scope."""
from chipbench import lfm2_layers

LAYER = "token mixers"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return lfm2_layers.scope_ms(ctx, ("mixer/attention_window",))
