"""host_stall_ms_max — host planning: over every round of the process that
went through `dispatch_round` and `finalize_round` and compiled nothing (the
count `compiles` on its `round/finalize` span is 0: in a traced run the warm
round and window rounds 1 to 3), the largest `host_ms` less their median:
near 0 in a quiet run, the stall in a run that met one on the host."""
from chipbench import accounts

LAYER = "host planning"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    return accounts.host_stall_ms(ctx)
