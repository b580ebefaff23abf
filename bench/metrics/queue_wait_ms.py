"""Mean time a request waited in the engine's queue before admission:
change of queue_wait_s (admission time minus arrival, summed over
admissions) over change of admitted."""
NAME, UNIT, SOURCE = "queue_wait_ms", "ms", "program_counter"
LAYER = "scheduler (runtime/scheduler.py, queueing.py)"
MOVES = "ttft_p95_s"


def compute(run):
    wait, n = run.delta.get("queue_wait_s"), run.delta["admitted"]
    return 1e3 * wait / n if wait is not None and n > 0 else None
