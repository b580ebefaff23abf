"""Output tokens that reached clients inside the window, over the
window's length (client clock)."""
NAME, UNIT, SOURCE, LAYER, MOVES = ("out_tok_per_s", "tokens/s", "host_clock",
                                    None, None)


def compute(run):
    n = sum(run.t_open <= t < run.t_close for r in run.reqs for t in r.times)
    return n / run.seconds
