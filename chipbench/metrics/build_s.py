"""build_s — entry and build: harness clock around `Experiment(...)` and
`block_until_ready` on its state (data made and put on the device)."""
LAYER = "entry and build"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    spans = ctx["spans"].get("build")
    return spans[0] if spans else None
