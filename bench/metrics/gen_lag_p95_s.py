"""How late the load generator ran: 95th percentile over the requests
due in the window of submit() returning minus the due time."""
from bench.record import p95

NAME, UNIT, SOURCE = "gen_lag_p95_s", "s", "host_clock"
LAYER = "client (bench/run.py, AsyncServer.submit)"
MOVES = "ttft_p95_s"


def compute(run):
    return p95(r.sent - r.sched for r in run.in_window() if r.sent is not None)
