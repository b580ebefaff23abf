"""Host time per prefill call over the window (Server._prefill_one: the
prefill program and the scatter into the pool, ending in a blocking read
of the logits): change of prefill_s over change of admitted."""
NAME, UNIT, SOURCE = "prefill_call_ms", "ms", "program_counter"
LAYER = "engine step (runtime/server.py)"
MOVES = "ttft_p95_s"


def compute(run):
    n = run.delta["admitted"]
    return 1e3 * run.delta["prefill_s"] / n if n > 0 else None
