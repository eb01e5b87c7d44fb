"""finalize_ms — fetch and record: median harness clock around
`finalize_round`, taken after `block_until_ready` on the round's outputs, so
it holds no device time."""
import statistics

LAYER = "fetch and record"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    spans = ctx["spans"].get("finalize")
    return 1e3 * statistics.median(spans) if spans else None
