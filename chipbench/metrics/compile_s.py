"""compile_s — round program, compile: the first call of the round program
(compile, or load from the persistent cache) less a steady call of it."""
LAYER = "round program compile"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    first, steady = ctx["spans"].get("first_round"), ctx["spans"].get("steady_round")
    if not first or not steady:
        return None
    return first[0] - min(steady)
