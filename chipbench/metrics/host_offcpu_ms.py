"""host_offcpu_ms — host planning: per traced round, host time in which the
round thread was not running (blocked in I/O, or off its core): the wall
time from the previous round's finalize to this one's (`wall_ns`) less the
round's `wait_ms` and `between_ms`, less the thread's CPU time over the same
stretch (`cpu_ns`); both counts from the `round/finalize` span."""
from chipbench import accounts

LAYER = "host planning"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return accounts.over(accounts.traced_rows(ctx), accounts.offcpu_ms)
