"""cache_load_s — round program compile: seconds the round program (`round_fn`)
took to come out of the persistent compile cache
(`xla/cache_retrieval_secs`); absent where the cache missed and the program
was compiled."""
from chipbench import phases

LAYER = "round program compile"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return (phases.compile_stages(ctx) or {}).get("xla/cache_retrieval_secs")
