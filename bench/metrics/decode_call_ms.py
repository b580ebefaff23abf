"""Host time per decode call over the window (Server._decode_tick's
device call and its blocking read of the (B, V) logits): change of
decode_s over change of ticks."""
NAME, UNIT, SOURCE = "decode_call_ms", "ms", "program_counter"
LAYER = "engine step (runtime/server.py)"
MOVES = "itl_p95_s"


def compute(run):
    n = run.delta["ticks"]
    return 1e3 * run.delta["decode_s"] / n if n > 0 else None
