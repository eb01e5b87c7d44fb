"""record_ms — fetch and record: median over the window's rounds of the
program's span `round/record` (`_record`, forensics, the telemetry flush,
the CSV/JSONL rows)."""
from chipbench import phases

LAYER = "fetch and record"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return phases.window_span_ms(ctx, "round/record", "finalize")
