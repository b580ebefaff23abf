"""Host time per decode tick spent copying the decode's logits to the
host and sampling the next tokens from them: change of readback_s plus
sample_s over change of ticks."""
NAME, UNIT, SOURCE = "readback_sample_ms", "ms", "program_counter"
LAYER = "sampling (runtime/server.py readback and _sample)"
MOVES = "itl_p95_s"


def compute(run):
    d = run.delta
    if "readback_s" not in d or "sample_s" not in d or d["ticks"] <= 0:
        return None
    return 1e3 * (d["readback_s"] + d["sample_s"]) / d["ticks"]
