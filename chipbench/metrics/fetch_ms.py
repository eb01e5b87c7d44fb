"""fetch_ms — fetch and record: median over the window's rounds of the
program's span `round/fetch` (`jax.device_get` of the round's payload). A
traced run's harness has already waited for the device, so this is the
transfer alone."""
from chipbench import phases

LAYER = "fetch and record"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.window_span_ms(ctx, "round/fetch", "finalize")
