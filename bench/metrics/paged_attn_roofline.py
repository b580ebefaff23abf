"""Paged decode attention's share of its roofline: the least time the
chip needs to read the KV rows the decoded tokens attend over, divided
by the summed device time of the kernel in the traced window.

The bytes are counted from request lengths, never from blocks fetched:
a token at output index j >= 1 of a request with an s-token prompt
attends over s + j rows of every layer, and those rows are divided over
the chips that share each layer. Attention is bound by bytes here (one
query row per KV head group), so HBM bandwidth sets the least time."""
from bench import work

NAME, UNIT, SOURCE = "paged_attn_roofline", "%", "device_trace"
LAYER = "kernels (kernels/paged_decode_attn.py)"
MOVES = "out_tok_per_s"
# The Pallas kernel's operations, by the name the compiler gives them.
KERNELS = {"paged_attn": r"_gqa_kernel|paged_gqa"}


def compute(run):
    if run.trace is None:
        return None
    t = run.trace["kernel_s"].get("paged_attn", 0.0)
    if t <= 0:
        return None
    t0, t1 = run.t_trace
    rows = sum(r.prompt_len + j for r in run.reqs
               for j, ts in enumerate(r.times) if j >= 1 and t0 <= ts < t1)
    nbytes = rows * work.kv_row_bytes(run.model) * run.model[
        "num_hidden_layers"] / run.tp
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t
