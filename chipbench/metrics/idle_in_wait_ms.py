"""idle_in_wait_ms — device: per traced round, device 0's idle time inside
the harness's `chipbench/device_wait` annotations (`trace.py`'s
`idle_by_span`): the device ran nothing while the host sat in
`block_until_ready`. Beside it, as a finding line read by no metric, what
the host's threads did in each such gap over 1 ms (`accounts.py`)."""
from chipbench import accounts

LAYER = "device"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return accounts.idle_in_wait_ms(ctx)
