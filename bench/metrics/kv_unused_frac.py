"""Share of the paged KV pool's rows that no live request held, over the
window's decode ticks: 1 - change of kv_live_row_ticks over change of
kv_pool_row_ticks (the pool's rows, kv_pool_blocks x kv_block_size,
summed over the same ticks)."""
NAME, UNIT, SOURCE = "kv_unused_frac", "frac", "program_counter"
LAYER = "paged-KV pool (runtime/paging.py)"
MOVES = "out_tok_per_s"


def compute(run):
    rows, pool = (run.delta.get(k) for k in
                  ("kv_live_row_ticks", "kv_pool_row_ticks"))
    if rows is None or not pool:
        return None
    return 1.0 - rows / pool
