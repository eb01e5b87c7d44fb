"""round_host_ms — host planning: median over the traced run's window rounds
of `host_ms` of the program's own round account
(`telemetry.round_accounts()`): the round's extent less its `round/wait` leaf
and the time in no span. In a traced run, where the harness waits for the
device between dispatch and finalize, what `dispatch_ms` + `finalize_ms` clock
from outside; the token cells' only reader of the host's cost a round."""
import statistics

from chipbench import accounts

LAYER = "host planning"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return accounts.over(accounts.window_rows(ctx), accounts.host_ms,
                         statistics.median)
