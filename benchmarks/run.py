"""Benchmark harness entrypoint: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV, and writes machine-readable
``BENCH_mlp.json`` / ``BENCH_serve.json`` artifacts (under --json-dir) so
the perf trajectory is tracked across PRs; CI's bench-smoke job pins the
deterministic modeled-HBM-bytes fields against a committed baseline.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--only fig17] [--json-dir .]
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=".",
                    help="where BENCH_*.json artifacts are written")
    args = ap.parse_args()

    from benchmarks import (
        fig4_redundant_ops, fig14_app_time, fig16_layerwise,
        fig17_sparsity_scaling, fig18_operand_order, fused_mlp,
        moe_structural, serve_cache_skip,
    )

    os.makedirs(args.json_dir, exist_ok=True)
    jp = functools.partial(os.path.join, args.json_dir)
    suites = [
        ("fig4", fig4_redundant_ops.run),
        ("fig14", fig14_app_time.run),
        ("fig16", fig16_layerwise.run),
        ("fig17", fig17_sparsity_scaling.run),
        ("fig18", fig18_operand_order.run),
        ("moe", moe_structural.run),
        ("fused_mlp",
         functools.partial(fused_mlp.run, json_path=jp("BENCH_mlp.json"))),
        ("serve_skip",
         functools.partial(serve_cache_skip.run,
                           json_path=jp("BENCH_serve.json"),
                           attn_json_path=jp("BENCH_attn.json"))),
        # Needs a forced multi-device host (XLA_FLAGS, see the module);
        # gracefully writes an empty artifact on a 1-device host.
        ("spmd",
         functools.partial(serve_cache_skip.run_spmd,
                           json_path=jp("BENCH_spmd.json"))),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        try:
            fn()
        except Exception:  # noqa: BLE001
            failed += 1
            sys.stderr.write(f"[{name}] FAILED\n{traceback.format_exc()}\n")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
