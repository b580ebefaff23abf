"""The engine's own host work per decode tick: the time inside
Server.step less what it spent in prefill calls, waiting for the decode
program, copying its logits and sampling. Change of step_s - prefill_s -
decode_wait_s - readback_s - sample_s over change of ticks."""
NAME, UNIT, SOURCE = "step_host_ms", "ms", "program_counter"
LAYER = "engine step (runtime/server.py)"
MOVES = "itl_p95_s"
PARTS = ("prefill_s", "decode_wait_s", "readback_s", "sample_s")


def compute(run):
    d = run.delta
    if any(k not in d for k in ("step_s",) + PARTS) or d["ticks"] <= 0:
        return None
    return 1e3 * (d["step_s"] - sum(d[k] for k in PARTS)) / d["ticks"]
