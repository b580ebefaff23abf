"""Set-up: process start to the end of warm-up (weights made, engine
built, every program of the cell compiled or loaded from the cache)."""
NAME, UNIT, SOURCE, LAYER, MOVES = "setup_s", "s", "host_clock", None, None


def compute(run):
    return run.setup_s
