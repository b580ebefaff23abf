"""Model FLOPs of every token processed in the traced window over what
the cell's chips could do in it at peak bf16: prompts prefilled (counted
when their first token arrived) and tokens decoded, each as 2 x the
matmul weights it passes through plus attention over its context, with
logits once per prefill and once per decoded token."""
from bench import work

NAME, UNIT, SOURCE = "mfu", "%", "device_trace"
LAYER = "model step (models/)"
MOVES = "out_tok_per_s"


def compute(run):
    if run.trace is None:
        return None
    t0, t1 = run.t_trace
    m = run.model
    flops = 0
    for r in run.reqs:
        for j, ts in enumerate(r.times):
            if t0 <= ts < t1:
                flops += (work.prefill_flops(m, r.prompt_len) if j == 0
                          else work.decode_token_flops(m, r.prompt_len + j))
    if flops == 0:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.trace["chips"]
    return 100.0 * flops / (run.trace["window_s"] * peak)
