"""plan_ms — host planning: median over the window's rounds of the program's
span `round/plan` (`select_agents`, `build_client_tasks`,
`build_batch_plan`)."""
from chipbench import phases

LAYER = "host planning"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.window_span_ms(ctx, "round/plan", "dispatch")
