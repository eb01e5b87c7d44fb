"""data_build_s — entry and build: the program's span `setup/data`: the host
draw or read of the dataset (`load_image_dataset` / `load_loan_dataset`),
without the device copy."""
from chipbench import phases

LAYER = "entry and build"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return phases.span_seconds(ctx, "setup/data")
