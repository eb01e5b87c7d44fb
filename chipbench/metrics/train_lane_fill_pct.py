"""train_lane_fill_pct — client step: real client-steps over the lane-steps
the loop executes (steps run x lanes), both summed over the window's rounds:
what packing lanes, or a narrower loop for one lane's tail, could still win.
A count, exact, from the `round/plan` span's counts."""
from chipbench import steps

LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return steps.window_total_pct(ctx, lambda c: c["lane_steps_real"],
                                  lambda c: c["steps_run"] * c["lanes"])
