"""local_battery_clean_jobs_pct — evaluation batteries: of the clean tests the
local battery runs (one a lane a segment), those that run as single-model
jobs, one model after another, and not under the `vmap` over the stacked
client models, both summed over the window's rounds: 100 where the engine's rule
(`fl/rounds.py::lanes_as_jobs`: an unsharded model with a convolution) gives
the job form, 0 where the stacked scan runs them. A count, exact, from the
`round/plan` span's counts; nothing from a program that does not count them."""
from chipbench import steps

LAYER = "evaluation batteries"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return steps.window_total_pct(
        ctx, lambda c: c.get("battery_clean_jobs", 0),
        lambda c: c.get("battery_clean_evals", 0))
