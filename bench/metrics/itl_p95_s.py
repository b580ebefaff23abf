"""95th percentile of every gap between two consecutive tokens of one
request whose later token arrived inside the window, over all requests
(client clock)."""
from bench.record import p95

NAME, UNIT, SOURCE, LAYER, MOVES = "itl_p95_s", "s", "host_clock", None, None


def compute(run):
    return p95(b - a for r in run.reqs for a, b in zip(r.times, r.times[1:])
               if run.t_open <= b < run.t_close)
