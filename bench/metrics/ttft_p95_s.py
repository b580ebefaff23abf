"""95th percentile, over every request due in the window, of first-token
arrival minus the request's due send time (client clock). A request that
failed, or had no token by the drain deadline, counts as the deadline."""
from bench.record import p95

NAME, UNIT, SOURCE, LAYER, MOVES = "ttft_p95_s", "s", "host_clock", None, None


def compute(run):
    return p95(run.ttft(r) for r in run.in_window())
