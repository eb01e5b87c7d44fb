"""lower_s — round program compile: seconds the round program (`round_fn`)
spent in jaxpr tracing and in lowering to an MLIR module, from the program's
`jax.monitoring` listener (`xla/trace_secs` + `xla/lower_secs`)."""
from chipbench import phases

LAYER = "round program compile"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    stages = phases.compile_stages(ctx)
    if not stages or "xla/lower_secs" not in stages:
        return None
    return stages.get("xla/trace_secs", 0.0) + stages["xla/lower_secs"]
