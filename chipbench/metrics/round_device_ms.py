"""round_device_ms — device: device busy time of the traced span over the
rounds traced."""
LAYER = "device"
UNIT = "ms"
MOVES = "client_updates_per_s"


def read(ctx):
    t, traced = ctx.get("trace"), ctx.get("traced")
    if not t or not traced or not traced["rounds"]:
        return None
    return 1e3 * t["busy_s"] / traced["rounds"]
