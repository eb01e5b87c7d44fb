"""train_narrow_steps_pct — client step: the real client-steps the program
runs one lane at a time (a lane's tail past the last step two lanes share:
the job loop's steps, read from the round's mask) over all real client-steps,
both summed over the window's rounds: how often the width-1 loop engages. A
count, exact, from the `round/plan` span's counts; nothing from a program that
does not count them."""
from chipbench import steps

LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    return steps.window_total_pct(
        ctx, lambda c: c.get("lane_steps_narrow", 0),
        lambda c: c["lane_steps_real"] if "lane_steps_narrow" in c else 0)
