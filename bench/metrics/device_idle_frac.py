"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 1 - busy / window."""
from bench import trace

NAME, UNIT, SOURCE = "device_idle_frac", "frac", "device_trace"
LAYER = "device (TPU v5e)"
MOVES = "out_tok_per_s"


def compute(run):
    if run.trace is None:
        return None
    return trace.idle_frac(run.trace["busy_s"], run.trace["window_s"])
