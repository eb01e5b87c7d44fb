"""train_steps_run_pct — client step: the steps the client step's loop runs
(those in which any lane holds a real batch: the trip count read from the
round's mask) over the steps of the static plan, both summed over the
window's rounds. A count, exact, from the `round/plan` span's counts."""
from chipbench import steps

LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return steps.window_total_pct(ctx, lambda c: c["steps_run"],
                                  lambda c: c["steps_plan"])
