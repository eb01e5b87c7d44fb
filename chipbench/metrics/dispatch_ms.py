"""dispatch_ms — host planning: median harness clock around `dispatch_round`
until it returns (selection, batch plan, enqueue of the round program)."""
import statistics

LAYER = "host planning"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    spans = ctx["spans"].get("dispatch")
    return 1e3 * statistics.median(spans) if spans else None
