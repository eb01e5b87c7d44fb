"""compile_cache_hits — round program, compile: programs this process took
from the persistent compile cache (jax.monitoring's cache_hits events)."""
LAYER = "round program compile"
UNIT = "count"
MOVES = "setup_s"


def read(ctx):
    return ctx["counters"].get("compile_cache_hits")
