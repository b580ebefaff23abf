"""Live slots per decode tick over the window, as a share of the batch:
change of decode_tokens over change of ticks times batch_slots."""
NAME, UNIT, SOURCE = "slot_occupancy", "frac", "program_counter"
LAYER = "scheduler (runtime/scheduler.py, queueing.py)"
MOVES = "out_tok_per_s"


def compute(run):
    ticks = run.delta["ticks"]
    if ticks <= 0:
        return None
    return run.delta["decode_tokens"] / (ticks * run.batch_slots)
