"""gc_pause_ms — fetch and record: per traced round, the time the
interpreter's collector held the process (`gc_pause_ns` on the
`round/finalize` span, from one `gc.callbacks` entry; previous finalize to
this one). 0 where a family freezes the heap before the window."""
from chipbench import accounts

LAYER = "fetch and record"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return accounts.over(accounts.traced_rows(ctx),
                         accounts.count("gc_pause_ns", 1e6))
