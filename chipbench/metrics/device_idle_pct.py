"""device_idle_pct — device: 1 - union of device-op intervals over the traced
span of a few steady rounds, from the profiler's trace."""
LAYER = "device"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
