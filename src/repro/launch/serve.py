"""Production serving launcher: continuous-batching prefill + decode.

    python -m repro.launch.serve --arch smollm-135m --requests 16 \
        [--reduced] [--max-new 32] [--mixed] [--sparce] [--eos-id N] \
        [--kv-block-size 16] [--kv-pool-blocks N] [--prefill-buckets 8,16,32] \
        [--attn-kernel gather|paged] [--prefix-cache] \
        [--shared-prefix-len N] [--open-loop] [--arrival-rate 8] \
        [--slo-ttft-ticks 64] [--slo-itl-ticks 8] [--mesh data,model]

--mixed draws per-request prompt lengths and decode budgets from a range
(the continuous batcher's target workload); --sparce turns on the SparCE
path for the serving MLPs and reports the realized tile-skip fraction.
For relu-family archs --sparce swaps the MLP activation to relu (the
paper's sparsity source). Gated-GLU archs (silu/gelu -- the DEFAULT
config family) keep their activation when --sparce-gate-threshold is
given: the gate activation's writeback emits a dead-tile bitmap
(|act(g)| <= tau) that skips both the up-projection compute and the
w_in/w_out stripe fetches. tau=0 is lossless (exact all-zero test; dead
batch slots still produce real skips); small calibrated taus trade
bounded output error for more skips.

Live admission: --open-loop serves the workload through the
``AsyncServer`` facade instead of one batch ``generate`` call -- a
background engine thread drains the admission queue while this process
submits requests with Poisson-spaced wall-clock gaps (--arrival-rate,
mean requests/second). --slo-ttft-ticks / --slo-itl-ticks set the
latency SLO (in decode-tick units, see docs/SERVING.md) the scheduler
enforces when deciding, each engine tick, whether to admit a prefill or
run the decode step; without them the engine admits greedily whenever a
slot and KV blocks are free.

KV paging: by default the server uses a PAGED KV cache -- a shared pool
of --kv-block-size-row blocks with per-slot block tables, so finished
requests return their blocks immediately and long/short requests share
HBM instead of each pinning max_len rows. This is the paper's "skip
without fetching" principle applied to the cache layer: SparCE only wins
because the fetch/issue machinery AROUND the skipped MACs is
reorganized; likewise, skipping a dead slot's decode work only saves HBM
if the cache stops reserving its tail. --kv-pool-blocks undersizes the
pool to oversubscribe (admission then waits on the free list, not on
slots x max_len); --kv-block-size 0 restores the contiguous layout.
Prompt lengths round up to --prefill-buckets (default: powers of two) so
the number of compiled prefill traces stays bounded under mixed traffic.

Decode attention: --attn-kernel paged runs decode attention as a Pallas
kernel straight out of the KV pool -- scalar-prefetched block tables +
lengths (the SASA-entry analogue) let it never DMA dead slots' blocks,
blocks past each live length, or null padding entries (each slot's live
blocks are copied in chunks, the PSRU's skip-before-fetch), instead of
materializing the full (B, max_blocks x block_size) gather every tick.
Token streams and skip statistics are identical to the default gather
path (CI-gated); metrics gain the realized block-skip fraction, modeled
attention HBM bytes saved and the kernel's chunk steps.

Prefix caching: --prefix-cache chain-hashes every prompt's full KV
blocks into an index after prefill; later requests sharing a prefix map
those pool blocks read-only and prefill only their divergent suffix
(copy-on-write forks a block when a full-prompt match must append).
--shared-prefix-len prepends a seeded common prefix of that many tokens
to every generated request so the flag has something to hit; telemetry
reports hit rate, blocks shared, CoW forks and modeled prefill ticks
saved. Token streams are identical with the cache on or off (CI-gated).

SPMD serving: --mesh DP,TP runs every prefill and decode tick shard_map'd
over a (data, model) device mesh -- batch slots and the paged KV pool
shard over 'data' (each data shard keeps its own block table, null block
and allocator: the per-core SASA), attention heads and FFN columns shard
over 'model' (one Megatron-style psum per block). Skip statistics are
psum'd across every shard before they land in the metrics, so the
printout (and the CI gates) read the same numbers as a single-host run
-- tested token-identical on CPU meshes. On a CPU host, force devices
first: XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mixed", action="store_true",
                    help="mixed-length workload: prompt lengths and "
                         "max_new budgets drawn per request")
    ap.add_argument("--sparce", action="store_true",
                    help="enable the SparCE path in serving MLPs "
                         "(skip-fraction metrics)")
    ap.add_argument("--sparce-mode", default="reference",
                    choices=("reference", "kernel", "fused"),
                    help="SparCE implementation for --sparce: 'fused' = "
                         "the MLP megakernel (bitmap at writeback, "
                         "VMEM-resident intermediate, w_out fetch skip)")
    ap.add_argument("--sparce-autotune", action="store_true",
                    help="let the engine replan MLP tiling/variant from "
                         "the measured (EMA) block sparsity")
    ap.add_argument("--sparce-gate-threshold", type=float, default=None,
                    help="gated-GLU (silu/gelu) dead-tile threshold tau: "
                         "keep the arch's GLU activation and skip gate "
                         "tiles with every |act(g)| <= tau (0 = exact "
                         "all-zero test, lossless). Implies --sparce. "
                         "Ignored by relu-family archs.")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="rows per paged-KV pool block; 0 = contiguous "
                         "per-slot max_len reservation (legacy layout)")
    ap.add_argument("--kv-pool-blocks", type=int, default=None,
                    help="usable KV pool blocks; default sizes the pool "
                         "for the worst case, smaller oversubscribes HBM "
                         "and admission waits on the free list")
    ap.add_argument("--prefill-buckets", default=None,
                    help="comma-separated prompt-length buckets (padded, "
                         "masked-tail prefill); default = powers of two "
                         "up to --max-len; 'off' = exact-length prefill")
    ap.add_argument("--attn-kernel", default="gather",
                    choices=("gather", "paged"),
                    help="decode attention over the paged pool: 'gather' "
                         "materializes full per-slot views (parity "
                         "oracle), 'paged' = fetch-skipping Pallas "
                         "kernel straight out of the KV pool (needs "
                         "--kv-block-size > 0)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical prompt prefixes across requests "
                         "as read-only KV pool blocks (chain-hashed "
                         "index, copy-on-write on append; needs "
                         "--kv-block-size > 0)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend a seeded common prefix of N tokens to "
                         "every request (a shared system prompt), the "
                         "workload --prefix-cache accelerates")
    ap.add_argument("--open-loop", action="store_true",
                    help="serve via AsyncServer: a background engine "
                         "thread drains the live queue while requests "
                         "arrive with Poisson wall-clock gaps")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="--open-loop mean arrival rate, requests/second")
    ap.add_argument("--slo-ttft-ticks", type=float, default=None,
                    help="time-to-first-token budget in decode-tick "
                         "units; enables SLO-aware admission scheduling")
    ap.add_argument("--slo-itl-ticks", type=float, default=None,
                    help="inter-token latency budget in decode-tick "
                         "units; prefills only interleave when they fit "
                         "this gap (or TTFT forces them)")
    ap.add_argument("--mesh", default=None,
                    help="'DP,TP' serving mesh: shard batch slots + KV "
                         "pool over DP data shards and heads/FFN over TP "
                         "model shards (needs DPxTP visible devices and "
                         "--kv-block-size > 0)")
    return ap.parse_args(argv)


def build_config(args: argparse.Namespace):
    """(arch config, ServeConfig) for parsed launcher arguments."""
    from repro.configs import get_config
    from repro.core.sparse_ops import SparsityConfig
    from repro.runtime.scheduler import SLOConfig
    from repro.runtime.server import ServeConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dp, tp = ((int(x) for x in args.mesh.split(","))
              if args.mesh is not None else (1, 1))
    sparsity = None
    sparce_on = args.sparce or args.sparce_gate_threshold is not None
    if sparce_on:
        glu_arch = cfg.mlp_act in ("silu", "gelu")
        if glu_arch and args.sparce_gate_threshold is not None:
            # Gated-GLU path: KEEP the arch's activation; sparsity comes
            # from thresholding the gate at its writeback instead of
            # from relufication.
            tau = args.sparce_gate_threshold
        else:
            # The paper's sparsity source is a ReLU-family MLP; swap the
            # act BEFORE init (relu MLPs are 2-matrix, no w_gate).
            cfg = dataclasses.replace(cfg, mlp_act="relu")
            tau = 0.0
        # block_m: one row tile covers the slots a data shard decodes
        # (at most one 8-row sublane tile), so a decode step streams each
        # weight stripe once and a tile is dead only when all of its
        # slots are. Under --mesh the f-tile must divide the PER-SHARD
        # d_ff slice (the engine rejects tilings that would break exact
        # skip-stat parity).
        block_m = max(1, min(8, args.batch_slots // dp))
        block_k = 128
        while tp > 1 and (cfg.d_ff // tp) % block_k:
            block_k //= 2
        sparsity = SparsityConfig(
            enabled=True, mode=args.sparce_mode, block_m=block_m,
            block_k=block_k,
            autotune=args.sparce_autotune, gate_threshold=tau,
        )
    buckets = None
    if args.prefill_buckets is not None:
        buckets = (
            () if args.prefill_buckets.strip().lower() == "off"
            else tuple(int(b) for b in args.prefill_buckets.split(","))
        )
    slo = None
    if args.slo_ttft_ticks is not None or args.slo_itl_ticks is not None:
        defaults = SLOConfig()
        slo = SLOConfig(
            target_ttft_ticks=(args.slo_ttft_ticks
                               if args.slo_ttft_ticks is not None
                               else defaults.target_ttft_ticks),
            target_itl_ticks=(args.slo_itl_ticks
                              if args.slo_itl_ticks is not None
                              else defaults.target_itl_ticks),
        )
    serve_cfg = ServeConfig(
        batch_slots=args.batch_slots, max_len=args.max_len,
        temperature=args.temperature, eos_id=args.eos_id,
        seed=args.seed, sparsity=sparsity,
        kv_block_size=args.kv_block_size,
        kv_pool_blocks=args.kv_pool_blocks,
        prefill_buckets=buckets, attn_kernel=args.attn_kernel,
        prefix_cache=args.prefix_cache, slo=slo,
        mesh=(dp, tp) if args.mesh is not None else None)
    return cfg, serve_cfg


def make_requests(args: argparse.Namespace, cfg) -> list:
    """The seeded workload: same arguments, same prompts and budgets."""
    from repro.runtime.server import Request

    rng = np.random.default_rng(args.seed)
    shared_prefix = None
    if args.shared_prefix_len > 0:
        # One seeded "system prompt" shared by every request -- the
        # workload shape prefix caching is built for.
        if cfg.frontend == "codes":
            shared_prefix = rng.integers(
                0, cfg.vocab_size,
                (cfg.num_codebooks, args.shared_prefix_len))
        else:
            shared_prefix = rng.integers(
                0, cfg.vocab_size, args.shared_prefix_len)
    reqs = []
    for i in range(args.requests):
        plen = args.prompt_len
        max_new = args.max_new
        if args.mixed:
            plen = int(rng.integers(max(1, args.prompt_len // 2),
                                    args.prompt_len + 1))
            max_new = int(rng.integers(max(1, args.max_new // 4),
                                       args.max_new + 1))
        if cfg.frontend == "codes":
            prompt = rng.integers(0, cfg.vocab_size, (cfg.num_codebooks, plen))
        else:
            prompt = rng.integers(0, cfg.vocab_size, plen)
        if shared_prefix is not None:
            prompt = np.concatenate([shared_prefix, prompt], axis=-1)
        reqs.append(Request(uid=i, prompt=prompt, max_new=max_new))
    return reqs


def main(argv=None):
    args = parse_args(argv)

    import jax

    from repro.models import model as model_lib
    from repro.runtime.server import AsyncServer, Server

    cfg, serve_cfg = build_config(args)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(args.seed))
    reqs = make_requests(args, cfg)
    # Open-loop arrival gaps draw from their own seeded stream.
    rng = np.random.default_rng([args.seed, 1])

    if args.open_loop:
        # Live-queue path: Poisson-spaced submissions against the
        # background engine thread, then a graceful drain + shutdown.
        asrv = AsyncServer(cfg, params, serve_cfg)
        gaps = rng.exponential(1.0 / max(args.arrival_rate, 1e-6),
                               size=len(reqs))
        t0 = time.perf_counter()
        for r, gap in zip(reqs, gaps):
            time.sleep(float(gap))
            asrv.submit(r.prompt, max_new=r.max_new, eos_id=r.eos_id,
                        uid=r.uid)
        done = asrv.drain()
        asrv.shutdown()
        dt = time.perf_counter() - t0
        m = asrv.metrics
        srv = asrv.server
    else:
        srv = Server(cfg, params, serve_cfg)
        t0 = time.perf_counter()
        done = srv.generate(reqs)
        dt = time.perf_counter() - t0
        m = srv.metrics
    # m is the typed ServeMetrics surface (repro/runtime/metrics.py):
    # attribute reads fail loudly on a typo instead of defaulting to 0.
    tok = m.decode_tokens
    print(f"served {len(done)} requests, {tok} decode tokens in "
          f"{m.ticks} ticks, {dt:.2f}s host wall clock incl. compile "
          f"({tok / max(dt, 1e-9):.1f} tok/s)")
    if m.mesh_data or m.mesh_model:
        print(f"  SPMD mesh: data={int(m.mesh_data)} x "
              f"model={int(m.mesh_model)} "
              f"(slots+KV pool sharded over data, heads/FFN over model; "
              f"skip stats below are psum'd across shards)")
    occ = tok / max(1, m.ticks * args.batch_slots)
    print(f"  slot occupancy {occ:.2f}, prefill {m.prefill_tokens} tok "
          f"/ {m.prefill_s:.2f}s, decode {m.decode_s:.2f}s (host clock "
          f"incl. compile)")
    if m.total_tile_dots:
        print(f"  SparCE mlp_skip_fraction={m.mlp_skip_fraction:.3f} "
              f"({m.skipped_tile_dots:.0f}/{m.total_tile_dots:.0f} "
              f"tile-dots)")
    if m.kv_paged:
        print(f"  paged KV: {int(m.kv_pool_blocks)} blocks x "
              f"{int(m.kv_block_size)} rows, peak in use "
              f"{int(m.kv_blocks_peak_in_use)} "
              f"(occupancy {m.kv_pool_peak_occupancy:.2f}, internal "
              f"frag {m.kv_internal_frag:.2f})")
        sf = m.kv_bytes_saved_frac
        # A worst-case-sized pool can exceed the contiguous figure by the
        # last block's rounding; call that what it is rather than
        # printing a negative saving.
        saved = (f"{sf:.1%} saved" if sf >= 0
                 else f"{-sf:.1%} block-rounding overhead; undersize with "
                      "--kv-pool-blocks to share HBM")
        print(f"  KV reserved {m.kv_bytes_reserved/1e6:.2f} MB paged vs "
              f"{m.kv_bytes_reserved_contiguous/1e6:.2f} MB contiguous "
              f"({saved}, "
              f"{m.kv_reserved_bytes_per_token/1e3:.1f} KB/token); "
              f"{int(m.prefill_traces)} prefill traces")
        if m.attn_blocks_total:
            realized = ("saved" if m.attn_kernel_paged
                        else "skippable (run --attn-kernel paged)")
            print(f"  decode attn: {int(m.attn_blocks_fetched)}/"
                  f"{int(m.attn_blocks_total)} pool-block fetches "
                  f"(skip {m.attn_block_skip_fraction:.1%}); "
                  f"{(m.attn_bytes_gather - m.attn_bytes_paged)/1e6:.2f}"
                  f" MB modeled HBM bytes {realized} vs full-view "
                  "gather")
        if m.attn_chunks:
            fill = m.attn_blocks_fetched / (m.attn_chunks
                                            * m.attn_chunk_blocks)
            print(f"  decode attn chunks: {int(m.attn_chunks)} steps of "
                  f"{int(m.attn_chunk_blocks)} blocks (fill {fill:.1%})")
    if m.prefix_cache_enabled:
        print(f"  prefix cache: {int(m.prefix_hits)}/"
              f"{int(m.prefix_lookups)} admissions hit "
              f"(rate {m.prefix_hit_rate:.1%}), "
              f"{int(m.prefix_matched_tokens)} prompt tokens served from "
              f"cache, {int(m.prefix_blocks_shared)} blocks shared, "
              f"{int(m.prefix_cow_forks)} CoW forks, "
              f"{int(m.prefix_evicted_blocks)} evicted")
        print(f"  prefix savings (modeled): "
              f"{m.prefill_ticks_saved:.2f}/{m.prefill_ticks_nocache:.2f} "
              f"prefill ticks ({m.prefill_ticks_saved_frac:.1%}), "
              f"{m.prefill_flops_saved/1e9:.2f} GFLOP of prefill skipped")
    if args.open_loop or serve_cfg.slo is not None:
        print(f"  queue: depth peak {int(m.queue_depth_peak)}, "
              f"admission {int(m.sched_admitted)} admitted / "
              f"{int(m.sched_deferred)} deferred / "
              f"{int(m.sched_forced)} TTFT-forced; "
              f"prefill tick share {m.prefill_tick_share:.2f}")
        print(f"  latency (virtual ticks): TTFT p50/p99 "
              f"{m.ttft_ticks_p50:.1f}/{m.ttft_ticks_p99:.1f}, "
              f"ITL p50/p99 "
              f"{m.itl_ticks_p50:.1f}/{m.itl_ticks_p99:.1f}; "
              f"SLO violations ttft={int(m.slo_ttft_violations)} "
              f"itl={int(m.slo_itl_violations)}")
    for r in done[:3]:
        s = r.stats
        print(f"  req {r.uid}: ttft={s['ttft_s']*1e3:.1f}ms "
              f"latency={s['latency_s']*1e3:.1f}ms tokens={int(s['tokens'])} "
              f"out={list(map(int, np.asarray(r.out).flat[:8]))}")
    return done


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    main()
