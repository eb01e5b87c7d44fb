"""stage_ms — host planning: median over the window's rounds of the program's
span `round/stage` (`np.stack` -> `jnp.asarray` of tasks, indices, mask and
sample counts)."""
from chipbench import phases

LAYER = "host planning"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.window_span_ms(ctx, "round/stage", "dispatch")
