"""record_kib — fetch and record: per traced round, what `Recorder.save`
wrote (`bytes` on the `round/record` span, counted in `_atomic_write`). The
recorder rewrites every CSV whole each round, so this grows with the round's
number: the metric fixes the size at the window's start."""
from chipbench import accounts

LAYER = "fetch and record"
UNIT = "KiB"
MOVES = "client_updates_per_s"


def read(ctx):
    return accounts.over(accounts.traced_rows(ctx),
                         accounts.count("bytes", 1024))
