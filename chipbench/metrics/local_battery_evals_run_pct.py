"""local_battery_evals_run_pct — evaluation batteries: the single-model tests
the local battery runs (the clean test of every lane plus one job per poison
row the recorder writes: the job loop's trip count, read from the round's
tasks) over the four parts for every lane, both summed over the window's
rounds. A count, exact, from the `round/plan` span's counts; nothing from a
program that does not count them."""
from chipbench import steps

LAYER = "evaluation batteries"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return steps.window_total_pct(ctx, lambda c: c.get("battery_evals_run", 0),
                                  lambda c: c.get("battery_evals_plan", 0))
