"""useful_step_share — client step: real client-steps over executed
client-steps in the window's rounds, from each round's batch-plan mask. A
count, exact: the static plan pads every client to the largest client's steps
and to the longest epoch count, and masked steps cost full compute."""
LAYER = "client step"
UNIT = "%"
MOVES = "client_updates_per_s"


def read(ctx):
    c = ctx["counters"]
    if not c.get("executed_client_steps"):
        return None
    return 100.0 * c["real_client_steps"] / c["executed_client_steps"]
