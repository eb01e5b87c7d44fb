"""train_slot_fill_pct — client step: real client-steps over the slots the
program executes for them (the full-width loop's steps x lanes, plus the steps
run one lane at a time), both summed over the window's rounds: what is still
run masked, and packing lanes could win. A count, exact, from the `round/plan`
span's counts; nothing from a program that does not count the two loops."""
from chipbench import steps

LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return steps.window_total_pct(
        ctx, lambda c: c["lane_steps_real"],
        lambda c: (c.get("steps_wide", 0) * c["lanes"]
                   + c.get("lane_steps_narrow", 0)))
