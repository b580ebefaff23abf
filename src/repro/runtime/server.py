"""Continuous-batching serving engine with live admission and SparCE skip
integration.

Engine shape (one ``step()``):

  1. **admission phase** -- while a slot is free and the queue head's
     worst-case KV-block commitment fits the pool
     (:meth:`BlockAllocator.try_reserve`, atomic), ask the
     :class:`~repro.runtime.scheduler.Scheduler` whether to spend the
     gap on a prefill or defer to the decode tick. An admitted request
     prefills alone (batch=1, prompt padded up to a small set of
     BUCKETS, logits gathered at the last REAL position) and its cache
     scatters into the free slot; its first token is sampled from the
     prefill logits. Bucketing bounds the number of jit traces at
     ``len(buckets)`` under arbitrary prompt-length traffic.
  2. **decode tick** -- ONE jitted :func:`model.serving_decode_step` for
     all slots, threading the active-slot mask through the model.
     Inactive slots' embeddings are zeroed, so under a ReLU-family MLP
     their activation rows are all-zero tiles and the SparCE bitmap path
     skips their GEMM tile-dots: a freed slot costs no MXU work -- the
     paper's dynamic zero-operand skipping applied to the serving hot
     path. ``decode_tokens`` counts only live slots.
  3. **release** -- a slot is freed the moment its request hits EOS or
     its ``max_new`` budget: its KV blocks return to the pool free list,
     its unused worst-case commitment is un-reserved (double-counting a
     released slot raises, see ``runtime/paging.py``), and the next
     queued request backfills it on a later admission phase.

Two front doors drive that step loop:

  * :meth:`Server.generate` -- the synchronous batch API of PR 1-3, now
    a thin wrapper: it enqueues the fixed request list and drains. With
    ``ServeConfig.slo`` unset the scheduler admits greedily, which is
    byte-for-byte the PR 1-3 engine schedule (token outputs, tick
    counts and SparCE skip statistics are pinned by the parity tests).
  * :class:`AsyncServer` -- live serving: a background engine thread
    drains a thread-safe :class:`~repro.runtime.queueing.RequestQueue`
    that clients feed through :meth:`AsyncServer.submit`; prefills are
    scheduled BETWEEN decode ticks under ``ServeConfig.slo``.

Clocks: every scheduling decision and every CI-gated latency statistic
runs on a deterministic VIRTUAL clock in decode-tick units (see
``runtime/scheduler.py``); wall-clock timings are reported alongside but
never consulted, so a seeded arrival trace reproduces its admission
order, TTFT/ITL percentiles and SLO-violation counts on any host.

KV layout: by default the caches are PAGED (``ServeConfig.kv_block_size``
rows per block, vLLM-style) -- a shared pool of fixed-size blocks plus a
host-side block table per slot, so long and short requests share HBM and
admission is gated on BLOCKS, not slots x max_len. The paper's "skip
without fetching" principle applied to the cache layer: the machinery
around the skip (here: admission, memory reservation) is reorganized so
the savings the skip earns are not given back as stranded cache rows.
``kv_block_size=0`` restores the contiguous per-slot layout; outputs and
skip statistics are token-identical across both (tested).

Prefix-cache block sharing (``ServeConfig.prefix_cache``): after every
prefill, the prompt's FULL blocks are chain-hashed into a
:class:`~repro.runtime.paging.PrefixCache`; a later admission whose
prompt shares a cached prefix maps those pool blocks into its table
READ-ONLY (one allocator ref each), prefills only the divergent suffix
(``_prefill_suffix``: prefix gathered from the pool, suffix run with
``continuation=True``), and copy-on-write forks the last block when the
FULL prompt matched and must take the re-run last row. The scheduler
prices a hit at the suffix bucket and the allocator commitment shrinks
by the shared blocks, so hits admit earlier AND cheaper -- while token
outputs stay bit-identical to the no-cache engine (tested).

Thread-safety: ``Server`` itself is single-threaded -- exactly one
thread may call ``start_engine``/``step``/``generate``. The safe
cross-thread surfaces are the queue (``enqueue`` via ``AsyncServer``'s
lock), the allocator's atomic reservations, and reading ``metrics``
values. ``AsyncServer`` serializes all engine work on its one background
thread.

Sampling is vectorized over the whole slot batch, so there is no
per-row Python sampling loop. Greedy sampling (``temperature <= 0``) is
the step program ``serve_sample``: the argmax runs on the device over the
step's logits and only the (slots,) int32 ids are copied to the host.
``temperature > 0`` copies the logits and draws Gumbel-max on the host
from the engine's seeded numpy stream. The server
reports engine metrics (ticks, active-token counts, realized MLP
tile-skip fraction, pool occupancy/fragmentation, prefill trace count,
queue depth, TTFT/ITL percentiles, SLO-violation counts, prefill/decode
tick shares) and per-request latency / throughput.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue as _queue_mod
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import cost_model, sasa
from repro.parallel import sharding as shard_lib
from repro.kernels.paged_decode_attn import (
    decode_attn_block_counts, decode_attn_chunks, gqa_chunk_blocks,
)
from repro.core.sparse_ops import SparsityConfig
from repro.models import model as model_lib
from repro.runtime.errors import (
    EngineFatal, RequestCancelled, RequestFailed, ServerOverloaded,
    ServingError, device_program, is_request_scoped,
)
from repro.runtime.faults import FaultInjector, FaultPlan, InjectedFault
from repro.runtime.metrics import ServeMetrics, span
from repro.runtime.paging import (
    BlockAllocator, PrefixCache, blocks_needed, pick_bucket,
    resolve_buckets,
)
from repro.runtime.queueing import QueuedRequest, RequestQueue
from repro.runtime.scheduler import Scheduler, SLOConfig

# Lazy block-growth alloc attempts per growth event: attempt 0 plus
# this many retries. Transient alloc faults clearing within the bound
# recover in place (faults_recovered); anything past it fails the ONE
# request whose growth it was (request-scoped, never engine-fatal).
_GROW_RETRIES = 3


def serve_sample(logits: jax.Array) -> jax.Array:
    """Greedy ids of a step's last-position logits: (N, 1, V) -> (N,), or
    (N, 1, K, V) -> (N, K) for codebooks. int32; the first index wins a
    tie, as in ``np.argmax``."""
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) or (K, S) for audio
    max_new: int = 32
    eos_id: Optional[int] = None  # overrides ServeConfig.eos_id
    # Per-request decode deadline: cancel after this many decode ticks
    # in a slot (across preemptions) even if max_new was not reached.
    # The partial output lands on ``out``; waiters see RequestCancelled.
    max_ticks: Optional[int] = None
    out: Optional[np.ndarray] = None
    # Set when the request failed (RequestCancelled / RequestFailed);
    # ``out`` then holds whatever tokens were produced before.
    error: Optional[BaseException] = None
    # Preemption state: every token produced before the last preemption
    # (set by the engine on requeue). On re-admission the engine
    # prefills prompt + resume_tokens, so the restored rollout is
    # token-identical to an uninterrupted one.
    resume_tokens: Optional[List[np.ndarray]] = None
    # Filled by the engine: ttft_s, latency_s, tokens, decode_ticks,
    # queue_ticks, ttft_ticks, itl_ticks_max (+ preemptions when > 0).
    stats: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ServeConfig:
    """Engine configuration. Field-level validation runs in
    ``__post_init__`` (bad configs are rejected at construction with
    actionable messages); checks that need the model family -- paged
    fallback, prefix-cache bucketability -- run in ``Server.__init__``,
    and per-request feasibility in ``Server._validate``."""

    batch_slots: int = 8
    max_len: int = 512
    temperature: float = 0.0  # 0 => greedy
    eos_id: Optional[int] = None
    seed: int = 0
    # SparCE integration for the serving path: when set, it replaces
    # cfg.sparsity for prefill+decode so the MLP GEMMs run sparce_matmul
    # with producer-fused ReLU bitmaps (and dead-slot rows skip).
    sparsity: Optional[SparsityConfig] = None
    # --- paged KV cache ---------------------------------------------------
    # Rows per KV pool block; 0 = legacy contiguous per-slot reservation.
    # (SSM/hybrid families fall back to contiguous automatically: their
    # recurrent state has no per-token rows to page.)
    kv_block_size: int = 16
    # Usable pool blocks (excluding the reserved null block). None sizes
    # the pool for the worst case (batch_slots full slots); smaller pools
    # oversubscribe HBM and admission waits on the free list instead.
    kv_pool_blocks: Optional[int] = None
    # Prefill buckets (prompt lengths round UP to the nearest bucket with
    # masked tail positions). None = powers-of-two up to max_len; () =
    # exact-length prefill (one trace per distinct prompt length).
    prefill_buckets: Optional[Tuple[int, ...]] = None
    # Decode-attention implementation over the paged pool: 'gather'
    # materializes the full (B, max_blocks*block_size) per-slot view
    # each tick then runs dense jnp attention (the parity oracle);
    # 'paged' runs the fetch-skipping Pallas kernel straight out of the
    # pool -- dead slots, blocks past each live length and null padding
    # entries are never DMA'd (kernels/paged_decode_attn.py). Outputs
    # and skip statistics are token-identical across both (tested).
    attn_kernel: str = "gather"
    # Prefix-cache block sharing: full prompt blocks are chain-hashed
    # into an index after prefill; a later admission maps the longest
    # cached prefix's pool blocks into its table READ-ONLY, prefills
    # only the divergent suffix, and copy-on-write forks a block when a
    # full-prompt match must append. Needs the paged layout (like
    # attn_kernel='paged') and a bucketable, patch-free family --
    # outputs stay token-identical to the no-cache engine (tested).
    prefix_cache: bool = False
    # --- SPMD serving mesh ------------------------------------------------
    # (data, model) mesh shape for SPMD serving: slots (and per-slot
    # lengths + pool blocks) shard over 'data', attention heads / FFN
    # columns over 'model'. None = single-host (the default). Needs the
    # paged layout and a bucketable family; prefill/decode then run
    # under shard_map with per-shard block tables (the null-block and
    # index-clamp fetch-skipping tricks are shard-local) and skip stats
    # are psum'd, so outputs AND skip counters match single-host exactly
    # (tests/test_spmd_serving.py).
    mesh: Optional[Tuple[int, int]] = None
    # --- live admission ---------------------------------------------------
    # Latency SLO the scheduler enforces when deciding, each engine tick,
    # whether to admit a prefill or run the decode tick. None = drain
    # mode: admit greedily whenever a slot + blocks are free (the PR 1-3
    # schedule; what Server.generate parity tests pin).
    slo: Optional[SLOConfig] = None
    # --- robustness -------------------------------------------------------
    # Bounded admission queue: pushes past this depth are SHED with a
    # typed ServerOverloaded instead of queueing into certain TTFT
    # misses. None = unbounded (the pre-robustness behaviour).
    max_queue_depth: Optional[int] = None
    # Preempt/requeue: allow the scheduler's reclaim policy to evict a
    # running lower-priority slot (releasing its KV blocks) when a
    # forced-TTFT head cannot get a slot or reserve blocks, requeueing
    # the victim for a restore re-prefill (suffix-only when the prefix
    # cache kept its written blocks). Needs the paged layout -- the
    # whole point is giving BLOCKS back. Off by default: the reclaim
    # path changes admission schedules, which the drain-mode parity
    # tests pin.
    preempt: bool = False
    # Seeded fault-injection plan (runtime/faults.py). None = no
    # injection; the containment machinery is always live either way.
    faults: Optional[FaultPlan] = None

    def __post_init__(self) -> None:
        if self.batch_slots < 1:
            raise ValueError(
                f"ServeConfig.batch_slots must be >= 1, got "
                f"{self.batch_slots}"
            )
        if self.max_len < 1:
            raise ValueError(
                f"ServeConfig.max_len must be >= 1, got {self.max_len}"
            )
        if self.kv_block_size < 0:
            raise ValueError(
                f"ServeConfig.kv_block_size must be >= 0 (0 = contiguous "
                f"layout), got {self.kv_block_size}"
            )
        if self.kv_pool_blocks is not None and self.kv_pool_blocks < 1:
            raise ValueError(
                f"ServeConfig.kv_pool_blocks must be >= 1 (or None for "
                f"the worst-case pool), got {self.kv_pool_blocks}"
            )
        if self.attn_kernel not in ("gather", "paged"):
            raise ValueError(
                f"ServeConfig.attn_kernel must be 'gather' or 'paged', "
                f"got {self.attn_kernel!r}"
            )
        if self.attn_kernel == "paged" and self.kv_block_size <= 0:
            raise ValueError(
                "ServeConfig.attn_kernel='paged' needs the paged KV "
                "layout: set kv_block_size > 0"
            )
        if self.prefix_cache and self.kv_block_size <= 0:
            raise ValueError(
                "ServeConfig.prefix_cache=True needs the paged KV "
                "layout: shared prefixes are pool blocks mapped into "
                "several tables, so set kv_block_size > 0"
            )
        if self.max_queue_depth is not None and self.max_queue_depth < 1:
            raise ValueError(
                f"ServeConfig.max_queue_depth must be >= 1 (or None "
                f"for unbounded), got {self.max_queue_depth}"
            )
        if self.preempt and self.kv_block_size <= 0:
            raise ValueError(
                "ServeConfig.preempt=True needs the paged KV layout "
                "(reclaim returns pool BLOCKS): set kv_block_size > 0"
            )
        if self.mesh is not None:
            dp, tp = self.mesh
            if dp < 1 or tp < 1:
                raise ValueError(
                    f"ServeConfig.mesh axes must be >= 1, got {self.mesh}"
                )
            if self.kv_block_size <= 0:
                raise ValueError(
                    "ServeConfig.mesh needs the paged KV layout (the "
                    "pool blocks are what shards over 'data'): set "
                    "kv_block_size > 0"
                )
            if self.batch_slots % dp:
                raise ValueError(
                    f"ServeConfig.mesh data axis {dp} must divide "
                    f"batch_slots={self.batch_slots} (slots shard "
                    "contiguously over 'data')"
                )


@dataclasses.dataclass
class _Slot:
    req: Request
    item: QueuedRequest
    produced: List[np.ndarray]
    t_admit: float
    t_first: float
    ticks: int = 0
    cache_len: int = 0  # rows currently in this slot's cache
    blocks: List[int] = dataclasses.field(default_factory=list)
    # Prefix-cache blocks mapped READ-ONLY into this slot's table (one
    # allocator ref each, released with the slot). Owned blocks in
    # ``blocks`` always sit AFTER the shared run in the table, so decode
    # writes (cache_len grows from the prompt end) never touch these.
    shared: List[int] = dataclasses.field(default_factory=list)
    commit: int = 0  # worst-case pool blocks promised to this request
    admit_vt: float = 0.0  # virtual time when prefill started
    first_vt: float = 0.0  # virtual time of the first token
    last_token_vt: float = 0.0
    itl_max: float = 0.0  # largest virtual-tick gap between tokens
    released: bool = False


@dataclasses.dataclass
class _EngineState:
    """Per-run device/pool state owned by the engine thread.

    ``allocs`` holds one :class:`BlockAllocator` per data shard (block
    ids are shard-LOCAL; tables too). ``alloc`` aliases ``allocs[0]``
    -- on a single host that is THE allocator, and it keeps the
    established introspection surface (tests, benches) working."""

    caches: Any
    alloc: Optional[BlockAllocator]
    tables: Optional[np.ndarray]
    slots: List[Optional[_Slot]]
    cur_tok: np.ndarray
    completed: List[Request]
    allocs: List[BlockAllocator] = dataclasses.field(default_factory=list)
    # Requests that FAILED (cancelled / request-scoped fault). Like
    # ``completed``, only accumulated for sync drivers -- an on_error
    # consumer (AsyncServer) takes them instead.
    failed: List[Request] = dataclasses.field(default_factory=list)


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


class Server:
    """Fixed-slot continuous batcher: scheduled admission, budgets, release.

    Single-threaded by contract (see module docstring); use
    :class:`AsyncServer` for live multi-threaded traffic. The stepwise
    surface (``start_engine`` / ``enqueue`` / ``step`` / ``any_active`` /
    ``finalize_metrics``) is what the open-loop harness and the async
    facade drive; ``generate`` composes them into the PR 1-3 batch API.
    """

    def __init__(self, cfg: ArchConfig, params, serve_cfg: ServeConfig):
        if serve_cfg.sparsity is not None:
            cfg = dataclasses.replace(cfg, sparsity=serve_cfg.sparsity)
        self.cfg, self.params, self.sc = cfg, params, serve_cfg
        self._paged = (
            serve_cfg.kv_block_size > 0
            and cfg.family in model_lib.paged_families()
        )
        # Value-level checks live in ServeConfig.__post_init__; the
        # family-coupled ones (paged fallback, bucketability) stay here.
        if serve_cfg.attn_kernel == "paged" and not self._paged:
            raise ValueError(
                "attn_kernel='paged' needs the paged KV layout (set "
                "kv_block_size > 0; ssm/hybrid families fall back to "
                "contiguous caches and must keep attn_kernel='gather')"
            )
        # Prompt rows share the cache with the (constant) patch prefix.
        self._patch_rows = (
            cfg.num_patches if cfg.frontend == "patches" else 0
        )
        if serve_cfg.prefix_cache:
            if not self._paged:
                raise ValueError(
                    "prefix_cache=True needs the paged KV layout (set "
                    "kv_block_size > 0; ssm/hybrid families have no "
                    "per-token rows to share)"
                )
            if (cfg.family not in model_lib.bucketable_families()
                    or self._patch_rows):
                raise ValueError(
                    f"prefix_cache=True is not supported for family "
                    f"{cfg.family!r}: suffix-only prefill needs bucketed "
                    "(masked-tail) prefill to be exact and a token-only "
                    "cache prefix (patch rows are per-request)"
                )
        if serve_cfg.preempt and not self._paged:
            raise ValueError(
                "preempt=True needs the paged KV layout (set "
                "kv_block_size > 0; ssm/hybrid families fall back to "
                "contiguous caches, which have no blocks to reclaim)"
            )
        self._max_rows = serve_cfg.max_len + self._patch_rows
        if self._paged:
            self._max_blocks = blocks_needed(
                self._max_rows, serve_cfg.kv_block_size)
            self._pool_usable = (
                serve_cfg.kv_pool_blocks
                if serve_cfg.kv_pool_blocks is not None
                else serve_cfg.batch_slots * self._max_blocks
            )
        else:
            self._max_blocks = 0
            self._pool_usable = 0
        if cfg.family in model_lib.bucketable_families():
            self._buckets = resolve_buckets(
                serve_cfg.prefill_buckets, serve_cfg.max_len)
        else:
            self._buckets = ()
        # Suffix-prefill scratch buffer: a bucketed suffix scattered
        # behind a near-full prefix can reach prefix + bucket rows, so
        # the continuation cache is statically oversized by the largest
        # bucket (rows past max_rows land in the null block on insert).
        self._ext_rows = self._max_rows + (
            max(self._buckets) if self._buckets else self._max_rows)
        # SPMD serving mesh (ServeConfig.mesh): slots shard over 'data',
        # heads/FFN over 'model'. The host keeps PER-SHARD allocators and
        # block tables in shard-LOCAL block units; every shard owns its
        # own null block 0, so the fetch-skipping tricks stay shard-local
        # (the paper's per-core SASA/SpRF). Single-host is the dp=tp=1
        # degenerate case of the same bookkeeping (base offset 0).
        self._mesh = None
        self._dp = self._tp = 1
        self._b_local = serve_cfg.batch_slots
        self._pool_shard = self._pool_usable
        self._pspecs = None
        if serve_cfg.mesh is not None:
            self._init_mesh()
        # Step fns memoised per sparsity bucket: re-entering a bucket the
        # engine has already planned for reuses its jitted fns (and their
        # trace caches) instead of recompiling -- an EMA hovering at a
        # bucket edge costs one retrace per DISTINCT bucket, not per flip.
        self._step_fn_cache: Dict[float, tuple] = {}
        self._build_step_fns()
        # Greedy sampling depends on no plan or mesh, so one program
        # (jit_serve_sample) serves every bucket: on a mesh the decode
        # logits stay sharded over 'data', each shard takes the argmax of
        # its own slots, and the ids gather in the host copy.
        self._sample_ids = jax.jit(serve_sample)
        # Planner-v2 feedback loop: EMA of the realized block sparsity
        # (from the aux skip accounting). When the bucketed estimate
        # crosses a bucket edge, the MLP plans are rebuilt from the new
        # measurement and the step functions re-jitted (one retrace per
        # bucket move; plans themselves come from the process cache).
        self._ema = sasa.SparsityEMA()
        self._rng = np.random.default_rng(serve_cfg.seed)
        self._prefill_shapes: set = set()
        # Live-admission machinery: virtual tick clock, cost model and
        # the SLO scheduler (drain mode when serve_cfg.slo is None).
        self._costs = cost_model.serve_tick_costs(cfg, serve_cfg.batch_slots)
        self._sched = Scheduler(self._costs, serve_cfg.slo,
                                max_queue_depth=serve_cfg.max_queue_depth)
        self._queue = RequestQueue(max_depth=serve_cfg.max_queue_depth)
        # Cross-thread cancellation surface: uids marked here are
        # collected by the engine thread at its next step boundary
        # (queued entries purged, live slots evicted + resources
        # released). The only state any thread may touch.
        self._cancel_lock = threading.Lock()
        self._cancel_pending: set = set()
        # Fault injector for the CURRENT run (rebuilt per start_engine
        # so reruns of the same plan re-inject the same faults).
        self._inj: Optional[FaultInjector] = None
        self._inj_seen = 0  # injector events already folded into metrics
        self._vt = 0.0
        self._vt_prefill = 0.0
        self._vt_decode = 0.0
        # Latency samples and the admission log are BOUNDED (rolling
        # windows) so a long-lived AsyncServer's RSS stays flat; the
        # percentiles become rolling-window percentiles once the caps
        # are hit, which no short-lived run (tests, benchmarks) reaches.
        self._ttft_ticks_all: deque = deque(maxlen=100_000)
        self._ttft_s_all: deque = deque(maxlen=100_000)
        self._itl_ticks_all: deque = deque(maxlen=500_000)
        self.admitted_uids: deque = deque(maxlen=100_000)  # admission order
        self._st: Optional[_EngineState] = None
        # Per-run prefix index (built in start_engine when enabled): maps
        # chain-hashed full prompt blocks to pool block ids. One index
        # per data shard (``_prefixes``); ``_prefix`` aliases shard 0's
        # -- the whole index on a single host.
        self._prefix: Optional[PrefixCache] = None
        self._prefixes: Optional[List[PrefixCache]] = None
        # AsyncServer hooks; called on the engine thread. on_error fires
        # for requests that FAIL (cancelled / request-scoped fault)
        # instead of finishing; the request's resources are already
        # released when it runs.
        self.on_token: Optional[Callable[[Request, np.ndarray], None]] = None
        self.on_finish: Optional[Callable[[Request], None]] = None
        self.on_error: Optional[
            Callable[[Request, BaseException], None]] = None
        # Typed metrics surface (runtime/metrics.py): every counter is a
        # documented dataclass field; the few config-derived ones are
        # stamped here, everything else starts at 0.0.
        self.metrics = ServeMetrics(
            kv_paged=float(self._paged),
            kv_block_size=float(
                serve_cfg.kv_block_size if self._paged else 0),
            kv_pool_blocks=float(self._pool_usable),
            attn_kernel_paged=float(serve_cfg.attn_kernel == "paged"),
            prefix_cache_enabled=float(serve_cfg.prefix_cache),
            mesh_data=float(self._dp if self._mesh is not None else 0),
            mesh_model=float(self._tp if self._mesh is not None else 0),
        )
        self._frag_sum = 0.0
        self._frag_ticks = 0
        self._occ_sum = 0.0
        self._attn_fetched = 0
        self._attn_total = 0
        self._attn_chunks = 0
        # Pool blocks one GQA kernel chunk holds, from the per-chip shapes
        # the kernel sees (0: the GQA kernel serves no tick).
        self._attn_chunk_blocks = 0
        if (self._paged and serve_cfg.attn_kernel == "paged"
                and cfg.mla is None):
            self._attn_chunk_blocks = gqa_chunk_blocks(
                self._max_blocks, serve_cfg.kv_block_size,
                cfg.num_kv_heads // self._tp, cfg.resolved_head_dim,
                jnp.dtype(cfg.dtype).itemsize)
        self.metrics.attn_chunk_blocks = float(self._attn_chunk_blocks)

    def _init_mesh(self) -> None:
        """Validate and build the (data, model) serving mesh.

        Hard requirements (fail loudly at construction, not mid-run):
        the paged layout (pool blocks are the 'data'-sharded resource), a
        bucketable family (the MoE router is not TP-aware), divisible
        head/FFN/pool/slot counts, and -- when SparCE is on -- sparsity
        tile shapes that divide the PER-SHARD GEMM dims, so the psum'd
        tile-dot totals equal the single-host totals EXACTLY (ceil()
        tile counts only distribute over even shards).

        Measured deployment: Mistral-Nemo-12B whole on mesh (1, 4) of a
        TPU v5e 2x2 host (the benchmark's ``mistral-nemo-12b-tp4.chat``):
        per chip a quarter of every layer (5.45 GB) plus the replicated
        embedding and head (2.68 GB), and a quarter of the KV heads of a
        32-slot, 2,048-row pool (2.69 GB, twice while a decode step runs);
        13.57 GB at peak on the fullest chip."""
        cfg, serve_cfg = self.cfg, self.sc
        dp, tp = serve_cfg.mesh
        if not self._paged:
            raise ValueError(
                f"ServeConfig.mesh needs the paged KV layout, but family "
                f"{cfg.family!r} fell back to contiguous caches"
            )
        if cfg.family not in model_lib.bucketable_families():
            raise ValueError(
                f"ServeConfig.mesh is not supported for family "
                f"{cfg.family!r}: tensor-parallel serving covers the "
                "dense/vlm/audio stacks (the MoE router is not TP-aware)"
            )
        # Raises on non-divisible heads/kv-heads/d_ff.
        shard_lib.serve_local_config(cfg, tp)
        if self._pool_usable % dp:
            raise ValueError(
                f"serving mesh data axis {dp} must divide the usable "
                f"pool ({self._pool_usable} blocks): each data shard "
                "owns an equal sub-pool (set kv_pool_blocks to a "
                f"multiple of {dp})"
            )
        sp = cfg.sparsity
        if sp is not None and sp.enabled:
            # Exact skip-stat parity: tile counts are ceil(dim/block),
            # which only distributes over shards when the blocks divide
            # the per-shard dims. Reject configs that would silently
            # change the psum'd totals instead of letting the CI parity
            # gate chase phantom regressions.
            if tp > 1 and (cfg.d_ff // tp) % sp.block_k:
                raise ValueError(
                    f"sparsity block_k={sp.block_k} must divide the "
                    f"per-shard d_ff ({cfg.d_ff}//{tp}="
                    f"{cfg.d_ff // tp}) for exact skip-stat parity "
                    "under tensor parallelism"
                )
            if dp > 1 and (serve_cfg.batch_slots // dp) % sp.block_m:
                raise ValueError(
                    f"sparsity block_m={sp.block_m} must divide the "
                    f"per-shard batch_slots ({serve_cfg.batch_slots}//"
                    f"{dp}={serve_cfg.batch_slots // dp}) for exact "
                    "skip-stat parity under data parallelism"
                )
        self._mesh = shard_lib.serve_mesh((dp, tp))
        self._dp, self._tp = dp, tp
        self._b_local = serve_cfg.batch_slots // dp
        self._pool_shard = self._pool_usable // dp
        self._pspecs = shard_lib.serve_param_specs(self.params, self._mesh)
        # Shard the (Megatron-column/row) params once up front; the step
        # fns' shard_map in_specs then see already-resident shards.
        self.params = jax.device_put(
            self.params,
            shard_lib.serve_param_shardings(self.params, self._mesh),
        )

    # --------------------------------------- host-side shard bookkeeping
    # Slots are CONTIGUOUS over 'data' (slot s lives on data shard
    # s // b_local, matching shard_map's chunking); each shard's
    # allocator hands out LOCAL block ids 1.._pool_shard with 0 as that
    # shard's own null block. Device-side pool ops that run OUTSIDE
    # shard_map (insert/copy/prefix-gather, plain GSPMD jits) take
    # GLOBAL flat ids: local + owner * (_pool_shard + 1) -- table
    # padding zeros map to the owner's null block, so junk writes stay
    # shard-local. The decode/prefill shard_map bodies instead take the
    # LOCAL tables as-is (each shard sees exactly its own sub-pool).
    # Single-host: one shard, base 0, local == global.
    def _slot_shard(self, slot: int) -> int:
        return slot // self._b_local

    def _slot_base(self, slot: int) -> int:
        return self._slot_shard(slot) * (self._pool_shard + 1)

    def _pool_shardings(self):
        """NamedShardings matching serve_paged_cache_specs for the pool
        (used to place the freshly initialized pool on the mesh)."""
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s),
            shard_lib.serve_paged_cache_specs(self.cfg),
        )

    def _build_step_fns(self) -> None:
        cfg, serve_cfg = self.cfg, self.sc
        key = (
            cfg.sparsity.expected_sparsity
            if cfg.sparsity is not None else 0.0
        )
        hit = self._step_fn_cache.get(key)
        if hit is not None:
            self._decode, self._prefill, self._prefill_cached = hit
            return
        if self._mesh is not None:
            self._build_mesh_step_fns(key)
            return
        # The step programs are named functions, so a profiler trace
        # names them jit_serve_decode / jit_serve_prefill /
        # jit_serve_prefill_cached (and PjitFunction(serve_...) on the
        # host), on one host and on a mesh alike.
        if self._paged:
            attn_kernel = serve_cfg.attn_kernel

            def serve_decode(p, toks, caches, active, tables):
                return model_lib.serving_decode_step(
                    p, cfg, toks, caches, active, tables,
                    attn_kernel=attn_kernel,
                )
        else:
            def serve_decode(p, toks, caches, active):
                return model_lib.serving_decode_step(
                    p, cfg, toks, caches, active
                )
        self._decode = jax.jit(serve_decode)
        paged = self._paged
        patch_rows = self._patch_rows

        def serve_prefill(p, batch):
            # Paged mode sizes the scratch cache at the (bucketed) prompt
            # itself -- the rows are immediately re-scattered into pool
            # blocks, so no max_len reservation ever exists. Contiguous
            # mode must match the big cache's row count for insertion.
            rows = batch["tokens"].shape[-1] + patch_rows
            small_len = rows if paged else serve_cfg.max_len + patch_rows
            caches = model_lib.init_caches(cfg, 1, small_len)
            logits, new_caches, aux = model_lib.forward(
                p, cfg, batch, caches, last_only=True
            )
            # aux['skip'] rides along so prefill GEMMs count toward the
            # skip metrics too, not just decode ticks.
            return logits, new_caches, aux["skip"]

        self._prefill = jax.jit(serve_prefill)

        if paged and serve_cfg.prefix_cache:
            ext_rows = self._ext_rows

            def serve_prefill_cached(p, batch, pool, block_ids,
                                     prefix_len):
                # Suffix-only continuation prefill: gather the matched
                # prefix rows out of the POOL into a batch=1 scratch
                # cache pinned at length=prefix_len, then run only the
                # (bucketed) suffix with continuation=True so its
                # queries attend over prefix + suffix. The scratch is
                # statically oversized (_ext_rows) so the suffix scatter
                # never clamps; the all-masked tail is an exact no-op in
                # the online softmax.
                small = model_lib.paged_prefix_caches(
                    pool, block_ids, prefix_len, ext_rows)
                logits, new_caches, aux = model_lib.forward(
                    p, cfg, batch, small, last_only=True,
                    continuation=True,
                )
                return logits, new_caches, aux["skip"]

            self._prefill_cached = jax.jit(serve_prefill_cached)
        else:
            self._prefill_cached = None
        self._step_fn_cache[key] = (
            self._decode, self._prefill, self._prefill_cached)

    def _build_mesh_step_fns(self, key: float) -> None:
        """SPMD step fns: the Pallas-containing prefill/decode forward
        bodies run under shard_map on the (data, model) mesh.

        The body runs the UNCHANGED single-host model code against the
        per-shard local config (1/tp heads + FFN columns, tp_axis set so
        attention/MLP close their row-sharded out-projections with one
        psum each): the paged pool chunk a data shard sees is exactly its
        own sub-pool with its own null block 0 at local index 0, and its
        LOCAL block tables index straight into it -- so the paged-attn
        kernel's scalar-prefetch clamp and the bitmap-gated MLP stripe
        DMAs skip fetches per shard exactly as they do on one host
        (per-core SASA, in the paper's terms). Skip stats are psum'd
        here (decode over both axes; prefill only over 'model' since it
        is replicated over 'data'), so `ServeMetrics` and its CI gates
        hold unchanged on a mesh. Pure-jnp pool maintenance ops
        (insert/copy/prefix-gather) are NOT shard_map'd: they stay plain
        GSPMD jits over the sharded pool, driven with global block ids
        (see the host-side shard bookkeeping notes above)."""
        cfg, serve_cfg = self.cfg, self.sc
        mesh = self._mesh
        # Recomputed from self.cfg on every (re)build, so an autotune
        # replan's new expected_sparsity reaches the shard bodies.
        cfg_l = shard_lib.serve_local_config(cfg, self._tp)
        pspecs = self._pspecs
        pool_specs = shard_lib.serve_paged_cache_specs(cfg)
        small_specs = shard_lib.serve_prefill_cache_specs(cfg)
        attn_kernel = serve_cfg.attn_kernel
        patch_rows = self._patch_rows

        # shard_map keeps the body's name: jit_serve_decode and so on.
        def serve_decode(p, toks, caches, active, tables):
            logits, new_caches, skip = model_lib.serving_decode_step(
                p, cfg_l, toks, caches, active, tables,
                attn_kernel=attn_kernel,
            )
            # Aggregated SpRF telemetry: per-shard tile/block skip
            # counters sum over slots shards AND FFN shards.
            return logits, new_caches, jax.lax.psum(
                skip, ("data", "model"))

        self._decode = jax.jit(jax.shard_map(
            serve_decode, mesh=mesh,
            in_specs=(pspecs, P("data"), pool_specs, P("data"),
                      P("data")),
            out_specs=(P("data"), pool_specs, P()),
            check_vma=False,
        ))

        def serve_prefill(p, batch):
            # Prefill is batch=1 and replicated over 'data' (every data
            # shard runs the same prompt); only the heads/FFN split over
            # 'model'. The scratch cache is built INSIDE the body at the
            # per-shard KV width.
            rows = batch["tokens"].shape[-1] + patch_rows
            caches = model_lib.init_caches(cfg_l, 1, rows)
            logits, new_caches, aux = model_lib.forward(
                p, cfg_l, batch, caches, last_only=True
            )
            # 'model' only: a psum over 'data' would dp-fold-count the
            # replicated prefill GEMMs.
            return logits, new_caches, jax.lax.psum(
                aux["skip"], ("model",))

        self._prefill = jax.jit(jax.shard_map(
            serve_prefill, mesh=mesh,
            in_specs=(pspecs, P()),
            out_specs=(P(), small_specs, P()),
            check_vma=False,
        ))

        if serve_cfg.prefix_cache:
            ext_rows = self._ext_rows

            def _suffix_body(p, batch, small):
                logits, new_caches, aux = model_lib.forward(
                    p, cfg_l, batch, small, last_only=True,
                    continuation=True,
                )
                return logits, new_caches, jax.lax.psum(
                    aux["skip"], ("model",))

            suffix_sm = jax.shard_map(
                _suffix_body, mesh=mesh,
                in_specs=(pspecs, P(), small_specs),
                out_specs=(P(), small_specs, P()),
                check_vma=False,
            )

            def serve_prefill_cached(p, batch, pool, block_ids,
                                     prefix_len):
                # The prefix gather runs GSPMD over the sharded pool
                # with GLOBAL block ids; the resulting small cache is
                # resharded to per-shard KV width by the shard_map
                # in_specs of the continuation body.
                small = model_lib.paged_prefix_caches(
                    pool, block_ids, prefix_len, ext_rows)
                return suffix_sm(p, batch, small)

            self._prefill_cached = jax.jit(serve_prefill_cached)
        else:
            self._prefill_cached = None
        self._step_fn_cache[key] = (
            self._decode, self._prefill, self._prefill_cached)

    def _maybe_replan(self) -> None:
        """Re-bucket the measured sparsity into the MLP planner input.

        Only acts when ``SparsityConfig.autotune`` is set; needs a couple
        of EMA updates before trusting the measurement. A replan swaps
        ``expected_sparsity`` (a static plan input) and rebuilds the
        jitted step functions -- the SASA plan cache keeps everything
        else memoised, so the cost is exactly one retrace."""
        sp = self.cfg.sparsity
        if sp is None or not (sp.enabled and sp.autotune):
            return
        bucket = self._ema.bucketed()
        if self._ema.updates >= 2 and bucket != sp.expected_sparsity:
            self.cfg = dataclasses.replace(
                self.cfg,
                sparsity=dataclasses.replace(sp, expected_sparsity=bucket),
            )
            self._build_step_fns()
            self.metrics.replans += 1

    # ------------------------------------------------------------ sampling
    def _sample(self, logits: jax.Array) -> np.ndarray:
        """Host token ids from a step's logits on the device, (N, 1, V)
        or (N, 1, K, V): (N,) or (N, K). Greedy runs ``serve_sample``
        and copies the ids; a temperature copies the logits and draws
        Gumbel-max on the host."""
        if self.sc.temperature <= 0:
            with device_program("sample"):
                return np.asarray(self._sample_ids(logits))
        last = np.asarray(logits, np.float32)[:, -1]
        z = last.astype(np.float64) / self.sc.temperature
        u = self._rng.random(z.shape)
        g = -np.log(-np.log(np.clip(u, 1e-12, 1.0)))
        return np.argmax(z + g, axis=-1)

    # ----------------------------------------------------------- admission
    def _request_need(self, r: Request) -> Tuple[int, int]:
        """(prompt_rows, worst_case_rows) a request puts in its cache.

        Decode tick j writes token j at row prompt+j-1; the final sampled
        token is never written, so the worst case is
        prompt + max(1, max_new) - 1 rows (plus the vlm patch prefix).
        A preempted request's effective prompt grew by its resume tokens
        while its remaining budget shrank by the same count, so its
        worst case is UNCHANGED from the original admission's.
        """
        rows0 = (int(self._effective_prompt(r).shape[-1])
                 + self._patch_rows)
        done = len(r.resume_tokens) if r.resume_tokens else 0
        return rows0, rows0 + max(1, r.max_new - done) - 1

    def _bucket_rows(self, r: Request) -> int:
        """Padded prompt rows a request's prefill will actually run at."""
        S = int(self._effective_prompt(r).shape[-1])
        S_pad = pick_bucket(S, self._buckets) if self._buckets else S
        return S_pad + self._patch_rows

    def _prefill_one(self, r: Request, slot: int, caches,
                     block_ids: Optional[List[int]] = None):
        """Prefill one request alone and scatter it into ``slot``.

        The prompt is padded up to its bucket (masked-tail positions):
        the cache length still advances by the TRUE length and logits are
        gathered at the last real position, so the result is the
        exact-length prefill up to float rounding while the jit trace
        count stays bounded by ``len(buckets)``.
        """
        cfg = self.cfg
        prompt = self._effective_prompt(r)
        S = int(prompt.shape[-1])
        S_pad = pick_bucket(S, self._buckets) if self._buckets else S
        if cfg.frontend == "codes":
            toks = np.zeros((1, cfg.num_codebooks, S_pad), np.int32)
            toks[0, :, :S] = prompt.reshape(cfg.num_codebooks, S)
        else:
            toks = np.zeros((1, S_pad), np.int32)
            toks[0, :S] = prompt.reshape(S)
        rows0 = S + self._patch_rows
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family in model_lib.bucketable_families():
            # Exact-length families (ssm/hybrid/moe) never pad, so their
            # prefill advances by S implicitly; forward rejects 'advance'
            # for them outright.
            batch["advance"] = jnp.asarray([rows0], jnp.int32)
        if cfg.frontend == "patches":
            batch["patch_embeds"] = jnp.zeros(
                (1, cfg.num_patches, cfg.d_model),
                jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
            )
        ids = None
        if self._paged:
            ids = np.zeros((self._max_blocks,), np.int32)
            ids[: len(block_ids)] = block_ids
            # Pool ops are GSPMD over the whole sharded pool and take
            # GLOBAL flat block ids; the table the decode kernel sees
            # stays shard-LOCAL. Padding zeros shift to the owner's
            # null block (base + 0), whose content is never read.
            ids += self._slot_base(slot)
        with span(self.metrics, "prefill_s", "serve.prefill", uid=r.uid):
            with device_program("prefill"):
                logits, small, skip = self._prefill(self.params, batch)
                if ids is not None:
                    caches = model_lib.insert_slot_paged(
                        caches, small, jnp.int32(slot), jnp.asarray(ids),
                        jnp.int32(rows0),
                    )
                else:
                    caches = model_lib.insert_slot_caches(
                        caches, small, slot)
                # The span holds the pool insert: the first token's
                # sample, queued behind it on the device, waits for it.
                jax.block_until_ready(caches)
                skip = np.asarray(skip, np.float64)
            # Host-side trace ledger: one entry per (jitted fn, shape), so
            # it counts replan retraces too and stays a faithful fallback
            # if the jit-cache probe (_cache_size, a private JAX API) ever
            # goes away.
            self._prefill_shapes.add(
                (id(self._prefill), cfg.frontend, S_pad))
        self.metrics.prefill_tokens += S
        self.metrics.admitted += 1
        self._count_prefill_skip(skip)
        # last_only logits, left on the device: (1, 1, V) or (1, 1, K, V).
        return logits, caches

    def _count_prefill_skip(self, skip) -> None:
        """Fold a prefill's (skipped, total) tile-dot pair into both the
        run totals and the prefill-phase slice (the slice lets parity
        checks compare the DECODE portion when suffix-only prefills
        legitimately run fewer GEMMs)."""
        skip = np.asarray(skip, np.float64)
        self.metrics.skipped_tile_dots += float(skip[0])
        self.metrics.total_tile_dots += float(skip[1])
        self.metrics.prefill_skipped_tile_dots += float(skip[0])
        self.metrics.prefill_total_tile_dots += float(skip[1])

    def _prefill_suffix(self, r: Request, slot: int, caches, table_row,
                        prefix_len: int, rows0: int):
        """Continuation prefill: run only the divergent suffix of a
        prompt whose leading ``prefix_len`` rows already sit in pool
        blocks mapped (read-only) into ``table_row``.

        The matched prefix is gathered out of the pool into a batch=1
        scratch cache pinned at length ``prefix_len``; the suffix is
        padded up to its own bucket and runs with ``continuation=True``
        so its queries attend over prefix + suffix at the right
        positions. The scratch is statically oversized (``_ext_rows``)
        so the bucketed scatter never clamps -- the all-masked tail is
        an exact no-op in the online softmax, which is what keeps the
        result bit-for-bit the full prefill's (tested)."""
        cfg = self.cfg
        prompt = self._effective_prompt(r)
        S = int(prompt.shape[-1])
        n_suffix = rows0 - prefix_len
        S_pad = (pick_bucket(n_suffix, self._buckets)
                 if self._buckets else n_suffix)
        if cfg.frontend == "codes":
            toks = np.zeros((1, cfg.num_codebooks, S_pad), np.int32)
            toks[0, :, :n_suffix] = prompt.reshape(
                cfg.num_codebooks, S)[:, prefix_len:]
        else:
            toks = np.zeros((1, S_pad), np.int32)
            toks[0, :n_suffix] = prompt.reshape(S)[prefix_len:]
        batch = {
            "tokens": jnp.asarray(toks),
            "advance": jnp.asarray([n_suffix], jnp.int32),
        }
        with span(self.metrics, "prefill_s", "serve.prefill", uid=r.uid):
            # The pool gather and suffix scatter are GSPMD ops over the
            # whole sharded pool: feed them GLOBAL flat ids (owner base +
            # local).
            g_row = (np.asarray(table_row, np.int32)
                     + np.int32(self._slot_base(slot)))
            with device_program("suffix prefill"):
                logits, small, skip = self._prefill_cached(
                    self.params, batch, caches, jnp.asarray(g_row),
                    jnp.int32(prefix_len),
                )
                caches = model_lib.insert_slot_paged_from(
                    caches, small, jnp.int32(slot), jnp.asarray(g_row),
                    jnp.int32(rows0), jnp.int32(prefix_len),
                )
                jax.block_until_ready(caches)
                skip = np.asarray(skip, np.float64)
            self._prefill_shapes.add(
                (id(self._prefill_cached), cfg.frontend, S_pad))
        # Only the suffix actually prefilled; the matched rows are the
        # prefix_matched_tokens counter's business.
        self.metrics.prefill_tokens += n_suffix
        self.metrics.admitted += 1
        self._count_prefill_skip(skip)
        return logits, caches

    def _finish(self, slot_state: _Slot, t_now: float):
        r = slot_state.req
        item = slot_state.item
        out = np.array(slot_state.produced[: r.max_new])
        r.out = out
        prior = r.stats  # preemption bookkeeping stashed by _preempt
        r.stats = {
            # Wall-clock figures are arrival-based (queue wait included);
            # the *_ticks figures are the deterministic virtual-clock
            # counterparts the SLO gate uses.
            "ttft_s": slot_state.t_first - item.arrival_s,
            "latency_s": t_now - item.arrival_s,
            "tokens": float(len(out)),
            "decode_ticks": float(slot_state.ticks),
            "queue_ticks": slot_state.admit_vt - item.arrival_vt,
            "ttft_ticks": slot_state.first_vt - item.arrival_vt,
            "itl_ticks_max": slot_state.itl_max,
        }
        if "preemptions" in prior:
            r.stats["preemptions"] = prior["preemptions"]
            r.stats["ttft_ticks"] = prior.get(
                "ttft_ticks", r.stats["ttft_ticks"])
            r.stats["ttft_s"] = prior.get("ttft_s", r.stats["ttft_s"])
        self.metrics.completed += 1

    def _hit_eos(self, r: Request, tok: np.ndarray) -> bool:
        eos = r.eos_id if r.eos_id is not None else self.sc.eos_id
        if eos is None:
            return False
        if self.cfg.frontend == "codes":
            return bool(np.all(tok == eos))
        return int(tok) == eos

    # -------------------------------------------------------------- engine
    def _validate(self, requests: List[Request]) -> None:
        """Reject requests that cannot EVER fit BEFORE admitting any: a
        slot's rows (prompt + decoded tokens) must fit max_len, and in
        paged mode the request's worst-case block need must fit the whole
        pool (otherwise it would wait on the free list forever)."""
        for r in requests:
            need = int(np.asarray(r.prompt).shape[-1]) + max(1, r.max_new)
            if need > self.sc.max_len:
                raise ValueError(
                    f"request uid={r.uid}: prompt + max_new = {need} "
                    f"tokens do not fit a max_len={self.sc.max_len} cache "
                    "slot; raise ServeConfig.max_len or lower max_new"
                )
            if self._paged:
                _, worst = self._request_need(r)
                nb = blocks_needed(worst, self.sc.kv_block_size)
                # On a mesh each slot lives entirely inside its owner
                # data shard's sub-pool, so the bound is per-shard.
                if nb > self._pool_shard:
                    what = (
                        f"{self._pool_shard}-block per-shard pool"
                        if self._mesh is not None
                        else f"{self._pool_usable}-block pool")
                    raise ValueError(
                        f"request uid={r.uid}: worst case {nb} KV blocks "
                        f"do not fit the {what}; "
                        "raise ServeConfig.kv_pool_blocks"
                    )

    # -------------------------------------------- stepwise engine surface
    def start_engine(self) -> None:
        """(Re)initialize the engine: fresh caches, pool, slots, queue.

        ``generate`` calls this per batch; ``AsyncServer`` calls it once
        before starting its engine thread. Metrics and latency samples
        accumulate across runs (matching the PR 1-3 behaviour of a
        reused ``Server``)."""
        cfg, sc = self.cfg, self.sc
        B = sc.batch_slots
        if self._paged:
            # One sub-pool (with its own null block 0) and one allocator
            # per data shard; single-host is the one-shard case, where
            # this is byte-identical to the PR 3 layout.
            n_blocks = self._dp * (self._pool_shard + 1)
            if self._mesh is None:
                caches = model_lib.init_paged_caches(
                    cfg, B, n_blocks, sc.kv_block_size)
            else:
                # Made in place, shard by shard: the whole pool need not
                # fit on one chip beside that chip's share of the weights.
                def serve_init_pool():
                    return model_lib.init_paged_caches(
                        cfg, B, n_blocks, sc.kv_block_size)

                caches = jax.jit(
                    serve_init_pool,
                    out_shardings=self._pool_shardings())()
            allocs = [
                BlockAllocator(self._pool_shard)
                for _ in range(self._dp)
            ]
            alloc: Optional[BlockAllocator] = allocs[0]
            tables = np.zeros((B, self._max_blocks), np.int32)
            # Fresh index per run: cached blocks belong to THIS pool.
            # Per data shard -- a prefix only ever matches blocks of the
            # pool chunk the sharing slots actually read.
            self._prefixes = (
                [PrefixCache(a, sc.kv_block_size) for a in allocs]
                if sc.prefix_cache else None
            )
            self._prefix = self._prefixes[0] if self._prefixes else None
        else:
            caches = model_lib.init_caches(cfg, B, self._max_rows)
            alloc, tables, allocs = None, None, []
            self._prefix, self._prefixes = None, None
        if cfg.frontend == "codes":
            cur_tok = np.zeros((B, cfg.num_codebooks), np.int32)
        else:
            cur_tok = np.zeros((B,), np.int32)
        if self._queue.depth():
            raise RuntimeError(
                "start_engine() with requests still queued: drain or "
                "discard the previous run first"
            )
        self._queue = RequestQueue(max_depth=sc.max_queue_depth)
        with self._cancel_lock:
            self._cancel_pending.clear()
        # Fresh injector per run: the fault schedule is a function of
        # (plan, workload), not of how many runs this Server has served.
        self._inj = FaultInjector(sc.faults) if sc.faults else None
        self._inj_seen = 0
        # Fresh virtual clock per run: serve_trace's determinism contract
        # (schedule = f(trace, config)) must hold on a REUSED Server too,
        # or past runs would push every new arrival into the past.
        self._vt = 0.0
        self._st = _EngineState(
            caches=caches, alloc=alloc, tables=tables,
            slots=[None] * B, cur_tok=cur_tok, completed=[],
            allocs=allocs,
        )

    def enqueue(self, r: Request, *, priority: float = 0.0,
                deadline_ticks: Optional[float] = None,
                arrival_vt: Optional[float] = None) -> QueuedRequest:
        """Queue a request for admission (arrival stamped at the current
        virtual time unless given). Thread-safe; the engine thread picks
        it up on its next admission phase.

        Raises :class:`ServerOverloaded` (typed backpressure, counted in
        ``metrics.shed_requests``) when the scheduler's shed policy or
        the queue's depth bound rejects the push -- nothing is enqueued
        and no engine resources were touched."""
        try:
            if self._sched.should_shed(self._queue.depth()):
                raise ServerOverloaded(
                    f"admission queue at max_depth="
                    f"{self.sc.max_queue_depth}: request uid={r.uid} "
                    "shed")
            return self._queue.push(
                r, priority=priority, deadline_ticks=deadline_ticks,
                arrival_vt=self._vt if arrival_vt is None else arrival_vt,
            )
        except ServerOverloaded:
            self.metrics.shed_requests += 1
            raise

    def cancel(self, uid: int) -> None:
        """Mark a submitted request cancelled. Thread-safe; the engine
        collects it at its next step boundary -- a queued entry is
        purged before admission, a live slot is evicted with its KV
        blocks released and the ledger re-checked. Unknown/finished
        uids are a no-op."""
        with self._cancel_lock:
            self._cancel_pending.add(uid)

    def queue_depth(self) -> int:
        return self._queue.depth()

    @property
    def failed(self) -> List[Request]:
        """Requests that failed (cancelled or request-scoped fault)
        this run, for sync drivers; empty when an ``on_error`` consumer
        takes them instead."""
        return list(self._st.failed) if self._st is not None else []

    def any_active(self) -> bool:
        st = self._st
        return st is not None and any(s is not None for s in st.slots)

    @property
    def vt(self) -> float:
        """The engine's virtual clock, in decode-tick units."""
        return self._vt

    def advance_vt(self, to_vt: float) -> None:
        """Advance the virtual clock across an IDLE gap (open-loop
        drivers use this to jump to the next arrival; never rewinds)."""
        self._vt = max(self._vt, float(to_vt))

    def _outstanding_commit(self, shard: Optional[int] = None) -> int:
        """Engine-side commitment ledger: blocks promised to live slots
        but not yet allocated. Cross-checked against the allocator's
        atomic counter every tick (mismatch == double-count bug).
        ``shard`` restricts the sum to one data shard's slots (each
        shard has its own allocator/ledger); None sums all."""
        st = self._st
        return sum(
            s.commit - len(s.blocks)
            for i, s in enumerate(st.slots)
            if s is not None
            and (shard is None or self._slot_shard(i) == shard)
        )

    def _record_first_token(self, s: _Slot) -> None:
        item = s.item
        ttft = s.first_vt - item.arrival_vt
        self._ttft_ticks_all.append(ttft)
        self._ttft_s_all.append(s.t_first - item.arrival_s)
        if ttft > self._sched.ttft_budget(item.deadline_ticks):
            self.metrics.slo_ttft_violations += 1

    def _emit_token(self, r: Request, tok: np.ndarray) -> None:
        if self.on_token is not None:
            self.on_token(r, tok)

    def _release_slot_resources(self, i: int, s: _Slot) -> None:
        """Return slot ``i``'s KV resources to the pool and clear the
        slot -- the shared tail of every way a slot can end (finish,
        cancel, request-scoped fault, preemption). Ends with the full
        structural + ledger check, so EVERY eviction path re-proves the
        pool invariants at the moment membership changes."""
        st = self._st
        s.released = True
        if self._paged:
            # Blocks/refs go back to the OWNER shard's allocator -- the
            # one every id in this slot's table is local to.
            alloc = st.allocs[self._slot_shard(i)]
            if s.blocks:
                alloc.release(s.blocks)
            if s.shared:
                # Drop this slot's refs on the read-only prefix blocks;
                # the prefix cache's own refs (and other sharers') keep
                # the registered blocks alive in the pool.
                alloc.release(s.shared)
                s.shared = []
            # Return the UNUSED tail of the worst-case commitment; the
            # allocator raises if this would double-count (released slot
            # already un-reserved). Shared blocks were never part of the
            # commitment, so the ledger math is unchanged by sharing.
            alloc.unreserve(s.commit - len(s.blocks))
            s.commit = len(s.blocks)
            st.tables[i, :] = 0
        st.slots[i] = None
        if self._paged:
            alloc.check(expect_reserved=self._outstanding_commit(
                self._slot_shard(i)))

    def _release(self, i: int) -> None:
        st = self._st
        s = st.slots[i]
        if s is None or s.released:
            raise RuntimeError(f"slot {i} released twice")
        self._finish(s, time.perf_counter())
        if self.on_finish is None:
            # Sync drivers (generate/serve_trace) read completions from
            # engine state; with an on_finish consumer (AsyncServer)
            # nothing accumulates here, so a long-lived engine's memory
            # stays flat.
            st.completed.append(s.req)
        self._release_slot_resources(i, s)
        if self.on_finish is not None:
            self.on_finish(s.req)

    def _record_failure(self, r: Request, err: ServingError) -> None:
        """Common bookkeeping for a request that FAILS instead of
        finishing: error + metrics classification, then hand it to the
        on_error consumer (or the sync ``failed`` list)."""
        r.error = err
        if isinstance(err, RequestCancelled):
            self.metrics.cancelled += 1
        else:
            self.metrics.failed_requests += 1
        if self.on_error is None:
            self._st.failed.append(r)
        else:
            self.on_error(r, err)

    def _fail_slot(self, i: int, err: ServingError) -> None:
        """Evict a LIVE slot whose request failed (cancel, deadline, or
        a contained request-scoped fault): resources released, ledger
        re-checked, partial output attached, waiters notified."""
        st = self._st
        s = st.slots[i]
        if s is None or s.released:
            raise RuntimeError(f"slot {i} failed after release")
        r = s.req
        r.out = np.array(s.produced[: r.max_new])  # partial tokens
        r.stats = {
            "tokens": float(len(r.out)),
            "decode_ticks": float(s.ticks),
            "queue_ticks": s.admit_vt - s.item.arrival_vt,
        }
        self._release_slot_resources(i, s)
        self._record_failure(r, err)

    def _fail_queued(self, item: QueuedRequest, err: ServingError) -> None:
        """Fail a request still in the queue (already popped/removed by
        the caller): no resources to release, just the bookkeeping."""
        item.req.out = np.zeros((0,), np.int32)
        self._record_failure(item.req, err)

    def _process_cancellations(self) -> None:
        """Collect cross-thread ``cancel()`` marks at a step boundary:
        purge queued entries, evict live slots. Runs before admission so
        a cancelled head never costs a prefill."""
        with self._cancel_lock:
            if not self._cancel_pending:
                return
            uids, self._cancel_pending = self._cancel_pending, set()
        for item in self._queue.remove_if(lambda e: e.req.uid in uids):
            self._fail_queued(item, RequestCancelled(
                f"request uid={item.req.uid} cancelled while queued"))
        st = self._st
        for i, s in enumerate(st.slots):
            if s is not None and s.req.uid in uids:
                self._fail_slot(i, RequestCancelled(
                    f"request uid={s.req.uid} cancelled mid-decode"))

    def _sync_fault_metrics(self) -> None:
        """Fold the injector's fire counter into the metrics surface
        (idempotent: only the delta since the last sync lands)."""
        if self._inj is not None and self._inj.injected > self._inj_seen:
            self.metrics.faults_injected += (
                self._inj.injected - self._inj_seen)
            self._inj_seen = self._inj.injected

    def _grow_one(self, owner: BlockAllocator, i: int,
                  s: _Slot) -> Optional[int]:
        """One lazy-growth block for slot ``i``, through the fault
        injector's allocator gate with a bounded retry loop: a
        transient fault clearing within ``_GROW_RETRIES`` retries is
        recovered invisibly; exhaustion (or a transient that outlives
        the retries) fails the REQUEST -- never the engine -- and
        returns None with the slot already evicted and the ledger
        re-proved."""
        uid = s.req.uid
        gidx = len(s.blocks)
        attempt = 0
        while True:
            try:
                if self._inj is not None:
                    self._inj.alloc_gate(uid, gidx, attempt)
                (blk,) = owner.alloc(1, reserved=True)
            except InjectedFault as e:
                self._sync_fault_metrics()
                if e.transient and attempt < _GROW_RETRIES:
                    attempt += 1
                    continue
                reason = (f"transient fault outlived {_GROW_RETRIES} "
                          "retries" if e.transient
                          else "injected allocator exhaustion")
                err = RequestFailed(
                    f"request uid={uid}: KV block growth failed "
                    f"({reason})")
                err.__cause__ = e
                self._fail_slot(i, err)
                self.metrics.faults_recovered += 1  # contained
                return None
            if attempt:
                # A transient fault cleared within the retry budget:
                # the request never notices.
                self.metrics.faults_recovered += 1
            self._sync_fault_metrics()
            return blk

    # ---------------------------------------------------- preempt/requeue
    def _full_sequence(self, r: Request,
                       produced: List[np.ndarray]) -> np.ndarray:
        """prompt ++ produced, in the prompt's token layout ((S,) or
        (K, S) for codes) -- the effective prompt a preempted request
        re-prefills with."""
        prompt = np.asarray(r.prompt)
        if not produced:
            return prompt
        if self.cfg.frontend == "codes":
            K = self.cfg.num_codebooks
            cols = [prompt.reshape(K, -1)]
            cols += [np.asarray(t).reshape(K, 1) for t in produced]
            return np.concatenate(cols, axis=-1)
        return np.concatenate(
            [prompt.reshape(-1),
             np.asarray(produced, np.int64).reshape(-1)])

    def _effective_prompt(self, r: Request) -> np.ndarray:
        """What this request's prefill must run over: the original
        prompt, plus every token produced before a preemption (the
        last of which is exactly the token whose decode tick the
        preemption squashed -- re-running it as the prefill's last row
        makes the restored next-token logits bit-identical to the
        uninterrupted decode's)."""
        if r.resume_tokens:
            return self._full_sequence(r, r.resume_tokens)
        return np.asarray(r.prompt)

    def _free_wait_ticks(self) -> float:
        """Ticks until the earliest NATURAL slot release (a live slot
        exhausting its max_new budget) -- the stall the reclaim policy
        weighs the restore re-prefill against. EOS may land earlier;
        this is the deterministic upper bound the cost model uses."""
        st = self._st
        rem = [s.req.max_new - len(s.produced)
               for s in st.slots if s is not None]
        return float(min(rem)) + 1.0 if rem else float("inf")

    def _restore_ticks(self, s: _Slot) -> float:
        """Modeled cost of restoring a victim later: the bucketed
        re-prefill of its effective prompt, minus what the prefix cache
        will serve from the blocks we register at preemption (full
        written blocks survive in the index unless evicted)."""
        rows_eff = s.cache_len + 1  # written rows + the unwritten token
        if self._prefix is not None:
            kept_rows = (s.cache_len // self.sc.kv_block_size
                         ) * self.sc.kv_block_size
            suffix = rows_eff - kept_rows
        else:
            suffix = rows_eff
        rows = pick_bucket(suffix, self._buckets) if self._buckets \
            else suffix
        return self._costs.prefill_ticks(rows)

    def _pick_victim(self, head_priority: float,
                     shard: Optional[int] = None) -> Optional[int]:
        """Reclaim victim: among live slots with priority STRICTLY below
        the head's (never preempt a peer -- the requeue boost would
        livelock two equals), the lowest-priority, most-recently-admitted
        one (least sunk prefix = cheapest squash). ``shard`` restricts
        to one data shard's slots -- blocks are only fungible within a
        shard's sub-pool."""
        st = self._st
        best: Optional[int] = None
        for i, s in enumerate(st.slots):
            if s is None or s.item.priority >= head_priority:
                continue
            if shard is not None and self._slot_shard(i) != shard:
                continue
            if best is None:
                best = i
                continue
            b = st.slots[best]
            if (s.item.priority, -s.admit_vt) < (b.item.priority,
                                                 -b.admit_vt):
                best = i
        return best

    def _preempt(self, i: int, head_priority: float) -> None:
        """Evict live slot ``i`` to fund a forced-TTFT admission.

        The victim's WRITTEN full blocks are registered in the prefix
        cache first (the index ref keeps them alive through the release
        below), so its restore prefill is suffix-only unless pool
        pressure LRU-evicts them meanwhile -- correct either way, just
        cheaper when they survive. The victim requeues with all its
        produced tokens as resume state at a priority strictly between
        its own and the head's: ahead of its peers, never ahead of the
        class that evicted it (which would starve the very admission
        this reclaim funds)."""
        st, sc = self._st, self.sc
        s = st.slots[i]
        r = s.req
        bs = sc.kv_block_size
        owner = self._slot_shard(i)
        prefix = (self._prefixes[owner]
                  if self._prefixes is not None else None)
        if prefix is not None:
            n_full = s.cache_len // bs
            if n_full:
                written = self._full_sequence(
                    r, s.produced)[..., : s.cache_len]
                keys = PrefixCache.chain_keys(written, bs)
                prefix.register(
                    keys[:n_full],
                    [int(b) for b in st.tables[i, :n_full]])
        self._release_slot_resources(i, s)
        r.resume_tokens = [np.asarray(t) for t in s.produced]
        boosted = (s.item.priority + head_priority) / 2.0
        self._queue.push(
            r, priority=boosted, arrival_vt=s.item.arrival_vt,
            arrival_s=s.item.arrival_s,
            deadline_ticks=s.item.deadline_ticks, force=True,
        )
        r.stats["preemptions"] = r.stats.get("preemptions", 0.0) + 1.0
        # TTFT is a property of the FIRST admission; stash it so the
        # eventual _finish (whose slot state only knows the restart)
        # reports the real figure. setdefault: a second preemption must
        # not overwrite the original sample with a restart's.
        r.stats.setdefault("ttft_ticks", s.first_vt - s.item.arrival_vt)
        r.stats.setdefault("ttft_s", s.t_first - s.item.arrival_s)
        self.metrics.preemptions += 1
        self.metrics.requeues += 1

    def _maybe_reclaim(self, item: QueuedRequest, *,
                       shard: Optional[int]) -> bool:
        """Ask the scheduler's reclaim policy whether to preempt a
        victim for queue head ``item``; preempt ONE if approved.
        ``shard=None`` reclaims a slot (any shard); a shard index
        reclaims BLOCKS, which must come from that shard's sub-pool.
        Returns True when a victim was evicted (caller retries)."""
        if not self.sc.preempt:
            return False
        v = self._pick_victim(item.priority, shard)
        if v is None:
            return False
        r = item.req
        pt = self._costs.prefill_ticks(self._bucket_rows(r))
        if not self._sched.should_reclaim(
                wait_ticks=self._vt - item.arrival_vt,
                prefill_ticks=pt,
                deadline_ticks=item.deadline_ticks,
                restore_ticks=self._restore_ticks(self._st.slots[v]),
                free_wait_ticks=self._free_wait_ticks()):
            return False
        self._preempt(v, item.priority)
        return True

    def _admission_phase(self) -> int:
        """Admit queue-head requests into free slots, scheduler-gated.

        Each free slot is considered once (matching the PR 1-3 loop); a
        head that does not fit the pool, or that the scheduler defers,
        blocks everything behind it -- deterministic head-of-line order.
        With ``ServeConfig.preempt`` on, a forced-TTFT head that finds
        no free slot (or no blocks for one) may first evict one
        lower-priority victim (``_maybe_reclaim``), and a
        request-scoped fault in the head's prefill fails THAT request
        only -- its resources roll back and admission retries with the
        next head. Returns the number of requests admitted."""
        st, sc = self._st, self.sc
        B = sc.batch_slots
        self._sched.begin_round()
        admitted = 0
        if (sc.preempt and self._queue.depth()
                and all(s is not None for s in st.slots)):
            # No free slot at all: ask the reclaim policy to evict ONE
            # victim for the head (any shard -- a slot is a slot). The
            # block-pressure form runs inside the per-slot loop below.
            head = self._queue.peek()
            if head is not None:
                self._maybe_reclaim(head, shard=None)
        for i in range(B):
            while st.slots[i] is None:
                res = self._try_admit_head(i)
                if res == "retry":
                    continue  # a preemption/containment changed state
                if res in ("full", "defer"):
                    return admitted  # head-of-line: nothing else moves
                if res == "admitted":
                    admitted += 1
                break  # admitted (possibly instantly released) or empty
        return admitted

    def _try_admit_head(self, i: int) -> str:
        """One attempt to admit the queue head into free slot ``i``.

        Returns ``"empty"`` (no head), ``"defer"`` (scheduler spends
        the gap on the decode tick), ``"full"`` (the pool cannot fund
        the head and reclaim did not help: wait for a release),
        ``"retry"`` (a victim was preempted or a faulted head was
        contained -- the caller retries this slot), or ``"admitted"``.
        """
        st, sc = self._st, self.sc
        item = self._queue.peek()
        if item is None:
            return "empty"
        r = item.req
        # A preempted request re-prefills its EFFECTIVE prompt (original
        # prompt ++ every token produced before eviction); everything
        # below prices and allocates for that sequence.
        resumed = bool(r.resume_tokens)
        rows0, worst = self._request_need(r)
        bs = sc.kv_block_size
        # Everything below runs against the OWNER data shard's
        # allocator/prefix index: slot i's blocks live in shard
        # i // b_local's sub-pool (one shard, the whole pool, on a
        # single host).
        owner = self._slot_shard(i)
        alloc = st.allocs[owner] if self._paged else None
        prefix = (
            self._prefixes[owner]
            if self._prefixes is not None else None
        )
        # Prefix-cache probe. lookup() RETAINS every matched block
        # on our behalf, so each bail-out path below must release
        # them or the pool leaks refs. A resumed request looks up its
        # effective prompt, so the blocks registered at its preemption
        # (if they survived) make the restore suffix-only.
        keys: List[bytes] = []
        shared: List[int] = []
        cow = False
        if prefix is not None:
            keys = PrefixCache.chain_keys(self._effective_prompt(r), bs)
            shared = prefix.lookup(keys)
            self.metrics.prefix_lookups += 1
            if shared:
                self.metrics.prefix_hits += 1
            # Full-prompt match: the block holding the last prompt
            # row is cached too, but that row must re-run for its
            # logits and the slot needs a writable home for it --
            # copy-on-write forks it into an owned block below.
            cow = bool(shared) and len(shared) * bs == rows0
        commit = 0
        if self._paged:
            # Shared blocks never need allocating, so they drop out
            # of the worst-case commitment (the CoW fork stays in:
            # its copy is an owned allocation).
            n_keep = len(shared) - 1 if cow else len(shared)
            commit = blocks_needed(worst, bs) - n_keep
            if not alloc.can_reserve(commit):
                # Relieve our own pressure first: LRU-evict blocks
                # only the index holds. Our matched blocks are
                # refcount >= 2 (index + our retain), so eviction
                # can never invalidate this lookup.
                if prefix is not None:
                    prefix.evict_for(commit)
                if not alloc.can_reserve(commit):
                    if shared:
                        alloc.release(shared)
                    # Block pressure: a victim on THIS shard can fund
                    # the head (its blocks return to the same sub-pool).
                    if self._maybe_reclaim(item, shard=owner):
                        return "retry"
                    return "full"  # pool full: wait for a release
        n_active = sum(1 for s in st.slots if s is not None)
        pt_full = self._costs.prefill_ticks(self._bucket_rows(r))
        n_suffix, prefix_len = rows0, 0
        if shared:
            n_suffix = 1 if cow else rows0 - len(shared) * bs
            prefix_len = rows0 - n_suffix
        suffix_rows = (pick_bucket(n_suffix, self._buckets)
                       if self._buckets else n_suffix)
        # A hit prices admission at the SUFFIX bucket: the scheduler
        # sees the work that actually runs, so cache-aware admission
        # falls out of the existing policy clauses unchanged.
        pt = (self._costs.prefill_ticks(suffix_rows) if prefix_len
              else pt_full)
        if not self._sched.admit_head(
                wait_ticks=self._vt - item.arrival_vt,
                prefill_ticks=pt, n_active=n_active,
                deadline_ticks=item.deadline_ticks):
            if shared:
                alloc.release(shared)
            return "defer"  # SLO defer: spend the gap on the decode tick
        block_ids: Optional[List[int]] = None
        if self._paged:
            # try_reserve is the ATOMIC form of the can_reserve probe
            # above; with a single engine thread they always agree,
            # and with concurrent reservers only this one counts.
            if not alloc.try_reserve(commit):
                if shared:
                    alloc.release(shared)
                if self._maybe_reclaim(item, shard=owner):
                    return "retry"
                return "full"
            if cow:
                # CoW fork: allocator bookkeeping first (atomic), then
                # the device-side row copy -- it must land BEFORE the
                # suffix prefill gathers the prefix through the table.
                # The device copy is a GSPMD op over the whole pool,
                # so the local ids shift to the owner's global base.
                block_ids = [alloc.fork(shared[-1], reserved=True)]
                src = shared.pop()
                base = self._slot_base(i)
                st.caches = model_lib.copy_pool_block(
                    st.caches, jnp.int32(block_ids[0] + base),
                    jnp.int32(src + base))
                self.metrics.prefix_cow_forks += 1
            else:
                block_ids = alloc.alloc(
                    blocks_needed(rows0, bs) - len(shared),
                    reserved=True)
            # Table layout: the read-only shared run first, owned
            # blocks after it -- decode appends (cache_len grows from
            # the prompt end) can only ever land in owned blocks.
            st.tables[i, : len(shared)] = shared
            st.tables[
                i, len(shared): len(shared) + len(block_ids)
            ] = block_ids
            # Sample the peak here too: requests that finish on
            # their prefill token never reach a decode tick but
            # still occupied pool blocks. Pool-wide: sum the shards.
            self.metrics.kv_blocks_peak_in_use = max(
                self.metrics.kv_blocks_peak_in_use,
                float(sum(a.in_use for a in st.allocs)))
        # By identity: a concurrent submit may have pushed a new,
        # higher-priority head between our peek and now.
        self._queue.pop_expected(item)
        t0 = time.perf_counter()
        admit_vt = self._vt
        try:
            if self._inj is not None and self._inj.poison(r.uid):
                self._sync_fault_metrics()
                raise InjectedFault("poison_prompt", r.uid)
            if prefix_len:
                last_logits, st.caches = self._prefill_suffix(
                    r, i, st.caches, st.tables[i], prefix_len, rows0)
            else:
                last_logits, st.caches = self._prefill_one(
                    r, i, st.caches, block_ids)
        except Exception as e:
            if not is_request_scoped(e):
                raise  # engine-fatal: the driver's classifier takes it
            # Containment: roll back EVERYTHING this admission took --
            # owned blocks, shared refs, the commitment tail -- and
            # re-prove the pool invariants before serving anyone else.
            if self._paged:
                if block_ids:
                    alloc.release(block_ids)
                if shared:
                    alloc.release(shared)
                alloc.unreserve(commit - len(block_ids or ()))
                st.tables[i, :] = 0
                alloc.check(
                    expect_reserved=self._outstanding_commit(owner))
            err = RequestFailed(
                f"request uid={r.uid} failed in prefill: {e}")
            err.__cause__ = e
            self._fail_queued(item, err)
            if isinstance(e, InjectedFault):
                self.metrics.faults_recovered += 1
            return "retry"
        self.metrics.queue_wait_s += t0 - item.arrival_s
        self._vt += pt
        self._vt_prefill += pt
        if resumed:
            # The realized squash penalty the reclaim cost model traded
            # against: ticks this restore prefill actually cost.
            self.metrics.preempt_restore_ticks += pt
        if prefix is not None:
            # Run-level savings model: pt_full is what the no-cache
            # engine would have spent on EVERY admission; saved is
            # the slice a hit kept off the virtual clock.
            self.metrics.prefill_ticks_nocache += pt_full
            if prefix_len:
                self.metrics.prefix_matched_tokens += prefix_len
                self.metrics.prefix_blocks_shared += len(shared)
                self.metrics.prefill_ticks_saved += pt_full - pt
                self.metrics.prefill_flops_saved += (
                    self._costs.prefill_flops(self._bucket_rows(r))
                    - self._costs.prefill_flops(suffix_rows))
            # Publish this prompt's FULL blocks. register() keeps the
            # incumbent block for an existing key, so a CoW fork's
            # private copy never displaces the shared original.
            n_full = rows0 // bs
            if n_full:
                prefix.register(
                    keys[:n_full],
                    [int(b) for b in st.tables[i, :n_full]])
        first = self._sample(last_logits)[0]  # () or (K,)
        # A restored request carries its pre-eviction tokens back in;
        # the prefill's last row re-ran the token whose decode tick the
        # preemption squashed, so ``first`` is exactly that tick's
        # output (bit-identical under greedy sampling -- the parity
        # test's claim).
        carried = ([np.asarray(t) for t in r.resume_tokens]
                   if resumed else [])
        s = _Slot(
            req=r, item=item, produced=carried + [np.asarray(first)],
            t_admit=t0, t_first=time.perf_counter(),
            cache_len=rows0,
            blocks=block_ids if block_ids is not None else [],
            shared=shared,
            commit=commit,
            admit_vt=admit_vt, first_vt=self._vt,
            last_token_vt=self._vt,
        )
        st.slots[i] = s
        st.cur_tok[i] = first
        self.admitted_uids.append(r.uid)
        if resumed:
            r.resume_tokens = None  # consumed: tokens live in the slot
        else:
            # TTFT belongs to the FIRST admission only; a restore
            # neither re-records nor re-emits the carried tokens.
            self._record_first_token(s)
        self._emit_token(r, np.asarray(first))
        if len(s.produced) >= r.max_new or self._hit_eos(
                r, np.asarray(first)):
            self._release(i)  # budget reached / instant EOS: reuse slot
        return "admitted"

    def _decode_tick(self) -> int:
        """One fused decode tick for all slots (dead slots masked).
        Returns the number of live slots it decoded for (0 = no-op)."""
        st, m = self._st, self.metrics
        with span(m, "decode_prep_s", "serve.decode.prep"):
            prep = self._prepare_tick()
        if prep is None:
            return 0
        active, n_active, step_toks = prep
        with span(m, "decode_s", "serve.decode"):
            with device_program("decode"):
                if self._paged:
                    logits, st.caches, skip = self._decode(
                        self.params, step_toks, st.caches,
                        jnp.asarray(active), jnp.asarray(st.tables),
                    )
                else:
                    logits, st.caches, skip = self._decode(
                        self.params, step_toks, st.caches,
                        jnp.asarray(active)
                    )
                with span(m, "decode_wait_s", "serve.decode.wait"):
                    jax.block_until_ready((logits, st.caches, skip))
                with span(m, "readback_s", "serve.decode.readback"):
                    skip = np.asarray(skip, np.float64)
        m.ticks += 1
        m.decode_tokens += n_active
        self._vt += 1.0
        self._vt_decode += 1.0
        m.skipped_tile_dots += float(skip[0])
        m.total_tile_dots += float(skip[1])
        self._ema.update(float(skip[0]), float(skip[1]))
        self._maybe_replan()

        with span(m, "sample_s", "serve.sample"):
            nxt = self._sample(logits)  # (B,) or (B, K), on the host
        if self.sc.temperature <= 0:
            m.device_sample_ticks += 1
        with span(m, "emit_s", "serve.emit"):
            self._emit_tick(nxt)
        return n_active

    def _prepare_tick(self):
        """The decode tick's host set-up: injected step faults, the
        active mask, lazy block growth, pool and attention-fetch
        accounting, the step tokens. Returns ``(active, n_active,
        step_toks)``, or None when no slot is live."""
        st, cfg, sc = self._st, self.cfg, self.sc
        if self._inj is not None:
            # Request-scoped step faults: one slot's host-side step
            # handling blows up; that slot is evicted (resources back,
            # ledger re-proved) and the tick carries on for the rest.
            for i, s in enumerate(st.slots):
                if s is None:
                    continue
                if self._inj.step_fault_one(s.req.uid, s.ticks):
                    self._sync_fault_metrics()
                    err = RequestFailed(
                        f"request uid={s.req.uid} hit an injected step "
                        f"fault at decode tick {s.ticks}")
                    err.__cause__ = InjectedFault("step", s.req.uid)
                    self._fail_slot(i, err)
                    self.metrics.faults_recovered += 1
        active = np.array(
            [s is not None for s in st.slots], np.float32
        )
        n_active = int(active.sum())
        if n_active == 0:
            return None
        if self._paged:
            # Lazy growth: a slot crossing a block edge claims its
            # next pool block only when the write reaches it. The
            # admission-time commitment guarantees the free list can
            # cover every live slot's growth -- only an INJECTED
            # allocator fault can make _grow_one fail.
            for i, s in enumerate(st.slots):
                if s is None:
                    continue
                # Owned blocks sit after the shared prefix run in the
                # table, so a write crossing a block edge lands in a NEW
                # owned block -- shared blocks never take decode writes.
                blk_idx = s.cache_len // sc.kv_block_size
                if blk_idx >= len(s.shared) + len(s.blocks):
                    owner = st.allocs[self._slot_shard(i)]
                    new_blk = self._grow_one(owner, i, s)
                    if new_blk is None:
                        continue  # growth failed: slot already evicted
                    s.blocks.append(new_blk)
                    st.tables[i, blk_idx] = new_blk
            # A growth fault may have evicted a slot after the count
            # above; the decode mask must reflect live membership.
            active = np.array(
                [s is not None for s in st.slots], np.float32)
            n_active = int(active.sum())
            if n_active == 0:
                return None
            self.metrics.kv_blocks_peak_in_use = max(
                self.metrics.kv_blocks_peak_in_use,
                float(sum(a.in_use for a in st.allocs)))
            # Commitment invariant, cheap per-tick form (two ints per
            # data shard): each shard allocator's atomic reservation
            # counter must equal the engine's per-slot ledger for the
            # slots it owns. The full structural check runs on every
            # release, where pool membership actually changes.
            for sh, a in enumerate(st.allocs):
                if a.reserved != self._outstanding_commit(sh):
                    raise AssertionError(
                        f"commitment ledger mismatch on shard {sh}: "
                        f"engine expects {self._outstanding_commit(sh)} "
                        f"outstanding, allocator holds {a.reserved}"
                    )
            used_rows = sum(
                s.cache_len + 1 for s in st.slots if s is not None)
            # Capacity counts each slot's MAPPED blocks (a shared block
            # once per sharer, like used_rows counts its rows), so the
            # unused-tail fraction stays in [0, 1] under prefix sharing
            # and index-only cached blocks don't dilute it. Identical to
            # alloc.in_use * block_size when the cache is off.
            cap_rows = sum(
                (len(s.shared) + len(s.blocks)) * sc.kv_block_size
                for s in st.slots if s is not None)
            if cap_rows:
                self._frag_sum += 1.0 - used_rows / cap_rows
                self._frag_ticks += 1
            self._occ_sum += (
                sum(a.in_use for a in st.allocs)
                / max(1, self._pool_usable))
            # Attention fetch accounting in block-table units: rows each
            # live slot attends over this tick (incl. the row this tick
            # writes) vs the full view the gather path materializes.
            eff = [0 if s is None else s.cache_len + 1 for s in st.slots]
            fetched, total = decode_attn_block_counts(
                eff, self._max_blocks, sc.kv_block_size)
            self._attn_fetched += fetched
            self._attn_total += total
            if self._attn_chunk_blocks:
                self._attn_chunks += decode_attn_chunks(
                    eff, sc.kv_block_size, self._attn_chunk_blocks)
        self.metrics.kv_live_row_ticks += sum(
            s.cache_len for s in st.slots if s is not None)
        self.metrics.kv_pool_row_ticks += (
            self.metrics.kv_pool_blocks * self.metrics.kv_block_size)
        cur_tok = st.cur_tok
        step = np.where(
            active.astype(bool)[:, None] if cur_tok.ndim > 1
            else active.astype(bool),
            cur_tok, 0,
        ).astype(np.int32)
        if cfg.frontend == "codes":
            step_toks = jnp.asarray(step)[..., None]  # (B, K, 1)
        else:
            step_toks = jnp.asarray(step)[:, None]  # (B, 1)
        return active, n_active, step_toks

    def _emit_tick(self, nxt: np.ndarray) -> None:
        """Per-slot bookkeeping of a decode tick's tokens ``nxt``: token
        callbacks, ITL stats, and immediate release on EOS/budget."""
        st, slo = self._st, self.sc.slo
        cur_tok = st.cur_tok
        for i in range(self.sc.batch_slots):
            s = st.slots[i]
            if s is None:
                continue
            tok = np.asarray(nxt[i])
            s.produced.append(tok)
            s.ticks += 1
            s.cache_len += 1  # this tick wrote cur_tok at cache_len
            gap = self._vt - s.last_token_vt
            s.last_token_vt = self._vt
            s.itl_max = max(s.itl_max, gap)
            self._itl_ticks_all.append(gap)
            if slo is not None and gap > slo.target_itl_ticks:
                self.metrics.slo_itl_violations += 1
            cur_tok[i] = tok
            self._emit_token(s.req, tok)
            if len(s.produced) >= s.req.max_new or self._hit_eos(
                    s.req, tok):
                self._release(i)
            elif (s.req.max_ticks is not None
                  and len(s.produced) - 1 >= s.req.max_ticks):
                # Per-request decode deadline. len(produced) - 1 counts
                # decode-tick-equivalents of progress past the first
                # token, and is preemption-invariant (a restore prefill
                # re-produces the squashed tick's token), so the budget
                # means the same thing whether or not the request was
                # ever evicted. Finishing (EOS/budget) wins above.
                self._fail_slot(i, RequestCancelled(
                    f"request uid={s.req.uid}: max_ticks="
                    f"{s.req.max_ticks} decode budget exhausted"))

    def step(self) -> Dict[str, int]:
        """One engine iteration: an admission phase, then (if anything
        is live) one decode tick. The caller loops this while
        ``queue_depth() or any_active()``."""
        if self._st is None:
            raise RuntimeError("start_engine() before step()")
        with span(self.metrics, "step_s", "serve.step"):
            self._process_cancellations()
            with span(self.metrics, "admit_s", "serve.admit"):
                admitted = self._admission_phase()
            decoded = self._decode_tick()
        return {"admitted": admitted, "decoded": decoded}

    def serve_trace(self, trace, *, priorities=None,
                    deadlines=None) -> List[Request]:
        """Serve an open-loop arrival trace deterministically.

        ``trace`` is ``[(arrival_vt, Request)]``: each request is
        enqueued exactly when the virtual clock reaches its arrival
        (idle gaps fast-forward the clock), so the whole run --
        admission order (``admitted_uids``), TTFT/ITL percentiles, SLO
        violations -- is a pure function of the trace and the config.
        This is THE open-loop driver: the scheduler tests and the
        CI-gated SLO benchmark both call it, so they measure the same
        schedule by construction. ``priorities``/``deadlines`` map
        ``uid -> priority / deadline_ticks``. Single-threaded, like the
        rest of the stepwise surface; use :class:`AsyncServer` for real
        wall-clock arrivals."""
        self._validate([r for _, r in trace])
        self.start_engine()
        items = sorted(enumerate(trace), key=lambda e: (e[1][0], e[0]))
        i = 0
        while True:
            while i < len(items) and items[i][1][0] <= self._vt + 1e-9:
                _, (avt, r) = items[i]
                try:
                    self.enqueue(
                        r, arrival_vt=float(avt),
                        priority=(priorities or {}).get(r.uid, 0.0),
                        deadline_ticks=(deadlines or {}).get(r.uid),
                    )
                except ServerOverloaded as e:
                    # Shed under the depth bound: counted (metrics +
                    # Request.error), the trace drives on -- load
                    # shedding exists exactly so overload does NOT
                    # take the serving loop down.
                    r.out = np.zeros((0,), np.int32)
                    r.error = e
                i += 1
            if self.queue_depth() == 0 and not self.any_active():
                if i >= len(items):
                    break
                self.advance_vt(items[i][1][0])  # idle to next arrival
                continue
            self.step()
        self.finalize_metrics()
        return list(self._st.completed)

    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve a fixed request list through the engine and drain it.

        A thin wrapper over the stepwise surface: with no SLO configured
        the scheduler admits greedily and this is schedule-identical to
        the PR 1-3 engine (tokens, ticks, skip stats -- all pinned by
        the parity tests). With ``ServeConfig.slo`` set the admission
        schedule interleaves under the SLO instead.
        """
        self._validate(requests)
        self.start_engine()
        for r in requests:
            self.enqueue(r)
        while self.queue_depth() or self.any_active():
            self.step()
        self.finalize_metrics()
        return list(self._st.completed)

    def prefill_trace_count(self) -> int:
        """Compiled prefill traces across all sparsity buckets -- the
        quantity prefill bucketing bounds (probed from the jit cache,
        cross-checked against the host-side shape set)."""
        n = 0
        for _, pre, pre_cached in self._step_fn_cache.values():
            for fn in (pre, pre_cached):
                cache_size = getattr(fn, "_cache_size", None)
                if cache_size is not None:
                    n += int(cache_size())
        return max(n, len(self._prefill_shapes))

    def finalize_metrics(self) -> ServeMetrics:
        """Fold the run's accumulators into ``metrics`` (skip fraction,
        KV-bytes model, queue depth, latency percentiles, SLO counts,
        tick shares, prefix-cache stats). Engine thread only; returns
        the typed :class:`ServeMetrics`."""
        m = self.metrics
        self._sync_fault_metrics()
        if m.total_tile_dots > 0:
            m.mlp_skip_fraction = m.skipped_tile_dots / m.total_tile_dots
        self._account_modeled_bytes()
        self._account_kv_bytes()
        m.queue_depth = float(self._queue.depth())
        m.queue_depth_peak = float(self._queue.depth_peak)
        for q in (50, 95, 99):
            setattr(m, f"ttft_ticks_p{q}", _pct(self._ttft_ticks_all, q))
            setattr(m, f"itl_ticks_p{q}", _pct(self._itl_ticks_all, q))
        m.ttft_s_p50 = _pct(self._ttft_s_all, 50)
        m.ttft_s_p99 = _pct(self._ttft_s_all, 99)
        m.sched_admitted = float(self._sched.admitted)
        m.sched_deferred = float(self._sched.deferred)
        m.sched_forced = float(self._sched.forced)
        vt_total = self._vt_prefill + self._vt_decode
        if vt_total > 0:
            m.prefill_tick_share = self._vt_prefill / vt_total
            m.decode_tick_share = self._vt_decode / vt_total
        if self._prefixes is not None:
            m.prefix_evicted_blocks = float(
                sum(p.evicted for p in self._prefixes))
            m.prefix_cache_blocks = float(
                sum(len(p) for p in self._prefixes))
        if m.prefix_lookups > 0:
            m.prefix_hit_rate = m.prefix_hits / m.prefix_lookups
        if m.prefill_ticks_nocache > 0:
            m.prefill_ticks_saved_frac = (
                m.prefill_ticks_saved / m.prefill_ticks_nocache)
        return m

    def _account_kv_bytes(self) -> None:
        """KV reservation telemetry: what the pool actually holds vs what
        the contiguous layout would have pinned for the same slots."""
        row_b = cost_model.kv_row_bytes(self.cfg)
        res = cost_model.kv_reservation_bytes(
            self.sc.batch_slots, self._max_rows, row_b,
            pool_blocks=self._pool_usable if self._paged else None,
            block_size=self.sc.kv_block_size if self._paged else 0,
        )
        m = self.metrics
        m.kv_bytes_reserved = float(res["paged"])
        m.kv_bytes_reserved_contiguous = float(res["contiguous"])
        m.kv_bytes_saved_frac = float(res["saved_frac"])
        generated = m.decode_tokens + m.admitted
        if generated:
            m.kv_reserved_bytes_per_token = float(res["paged"]) / generated
        if self._pool_usable:
            m.kv_pool_peak_occupancy = (
                m.kv_blocks_peak_in_use / self._pool_usable)
        if self._frag_ticks:
            m.kv_internal_frag = self._frag_sum / self._frag_ticks
        if m.ticks:
            m.kv_pool_mean_occupancy = self._occ_sum / m.ticks
        m.prefill_traces = float(self.prefill_trace_count())
        self._account_attn_bytes(row_b)

    def _account_attn_bytes(self, row_bytes: int) -> None:
        """Decode-attention fetch model: pool blocks the paged kernel
        DMAs vs the full view the gather path materializes, translated
        to HBM bytes across all attention layers.
        ``modeled_attn_bytes_saved`` is REALIZED savings -- nonzero only
        when the paged kernel actually served the ticks; the skip
        fraction is reported either way (it is what the kernel would
        skip, a property of the lengths/tables alone)."""
        m = self.metrics
        m.attn_blocks_fetched = float(self._attn_fetched)
        m.attn_blocks_total = float(self._attn_total)
        m.attn_chunks = float(self._attn_chunks)
        if not self._attn_total:
            return
        by = cost_model.decode_attn_hbm_bytes(
            blocks_fetched=self._attn_fetched,
            blocks_total=self._attn_total,
            block_size=self.sc.kv_block_size, row_bytes=row_bytes,
        )
        m.attn_block_skip_fraction = (
            1.0 - self._attn_fetched / self._attn_total)
        m.attn_bytes_gather = float(by["gather"])
        m.attn_bytes_paged = float(by["paged"])
        m.attn_bytes_saved_frac = float(by["saved_frac"])
        if self.sc.attn_kernel == "paged":
            m.modeled_attn_bytes_saved = float(by["gather"] - by["paged"])

    def _account_modeled_bytes(self) -> None:
        """Explainability metric: HBM bytes the fused MLP megakernel saves
        vs the pre-fused pipeline at the REALIZED skip fraction, per the
        cost model, over all decode-tick MLPs served. (Prefill GEMMs run
        at different M per prompt and are left out of the model.)
        relu-family MLPs compare fused vs two_kernel; gated-GLU
        (silu/gelu) MLPs compare the GLU megakernel vs the unfused
        3-GEMM pipeline."""
        sp, cfg = self.cfg.sparsity, self.cfg
        if (
            sp is None or not sp.enabled or cfg.family not in
            ("dense", "vlm", "audio")
            or cfg.mlp_act not in ("relu", "relu2", "silu", "gelu")
        ):
            return
        dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
        if cfg.mlp_act in ("silu", "gelu"):
            by = cost_model.glu_mlp_hbm_bytes(
                self.sc.batch_slots, cfg.d_model, cfg.d_ff, cfg.d_model,
                block_sparsity=self.metrics.mlp_skip_fraction,
                dtype_bytes=dtype_bytes, block_m=sp.block_m,
            )
            saved = by["unfused"] - by["fused"]
        else:
            by = cost_model.mlp_hbm_bytes(
                self.sc.batch_slots, cfg.d_model, cfg.d_ff, cfg.d_model,
                block_sparsity=self.metrics.mlp_skip_fraction,
                dtype_bytes=dtype_bytes, block_m=sp.block_m,
            )
            saved = by["two_kernel"] - by["fused"]
        self.metrics.modeled_hbm_bytes_saved = float(
            saved * cfg.num_layers * self.metrics.ticks
        )


# ------------------------------------------------------------ async facade
_STREAM_END = object()


class Submission:
    """Handle for one :meth:`AsyncServer.submit`: stream tokens as they
    are produced, or block for the finished :class:`Request`.

    Thread-safe: the engine thread feeds ``_tokens``/``_done``; any
    client thread may consume :meth:`stream` / :meth:`result` (but only
    ONE consumer per handle -- tokens are handed out once)."""

    def __init__(self, request: Request):
        self.request = request
        self._tokens: _queue_mod.Queue = _queue_mod.Queue()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None

    @property
    def uid(self) -> int:
        return self.request.uid

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def stream(self, timeout: Optional[float] = None) -> Iterator[np.ndarray]:
        """Yield tokens as the engine produces them; returns at EOS /
        budget. ``timeout`` bounds the wait for EACH token (raises
        ``TimeoutError``, like :meth:`result`)."""
        while True:
            try:
                tok = self._tokens.get(timeout=timeout)
            except _queue_mod.Empty:
                raise TimeoutError(
                    f"request uid={self.request.uid}: no token within "
                    f"{timeout}s") from None
            if tok is _STREAM_END:
                if self._error is not None:
                    raise self._error
                return
            yield tok

    def result(self, timeout: Optional[float] = None) -> Request:
        """Block until the request finishes; returns it with ``out`` and
        ``stats`` filled."""
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(
                f"request uid={self.request.uid} not finished in time")
        if self._error is not None:
            raise self._error
        return self.request


class AsyncServer:
    """Live-queue serving facade: a background engine thread drains a
    thread-safe admission queue while clients ``submit()`` concurrently.

    * :meth:`submit` validates, enqueues and returns a
      :class:`Submission` handle (callable from any thread).
    * :meth:`stream` / ``Submission.stream`` yield tokens as decode
      ticks produce them.
    * :meth:`drain` blocks until everything submitted so far has
      finished and returns the completed requests (since the last
      drain), with ``metrics`` finalized.
    * :meth:`shutdown` (or ``with AsyncServer(...) as srv:``) drains and
      stops the engine thread gracefully.

    All model/cache state is owned by the one engine thread; the only
    shared surfaces are the queue, the handle table (under ``_lock``)
    and the allocator's atomic reservations. An engine-thread exception
    fails all outstanding handles and stops the engine; it re-raises
    from ``drain``/``result``.
    """

    def __init__(self, cfg: ArchConfig, params, serve_cfg: ServeConfig,
                 *, start: bool = True):
        self._srv = Server(cfg, params, serve_cfg)
        self._srv.start_engine()
        self._srv.on_token = self._on_token
        self._srv.on_finish = self._on_finish
        self._srv.on_error = self._on_error
        self._lock = threading.RLock()
        self._wake = threading.Condition(self._lock)
        self._handles: Dict[int, Submission] = {}
        self._completed: List[Request] = []
        self._failed: List[Request] = []
        self._stop = False
        self._started = False
        self._engine_error: Optional[BaseException] = None
        self._auto_uid = itertools.count()
        self._thread = threading.Thread(
            target=self._engine_loop, name="serve-engine", daemon=True)
        if start:
            self.start()

    # Engine lifecycle -----------------------------------------------------
    def start(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
        self._thread.start()

    def _work_pending(self) -> bool:
        return self._srv.queue_depth() > 0 or self._srv.any_active()

    def _engine_loop(self) -> None:
        while True:
            with self._wake:
                while not self._stop and not self._work_pending():
                    self._wake.wait(timeout=0.05)
                if self._stop:
                    # Prompt exit: shutdown(drain=True) already drained,
                    # and shutdown(drain=False) means abort -- it fails
                    # any outstanding handles after joining us.
                    break
            try:
                self._srv.step()
            except BaseException as e:  # noqa: BLE001 - fail all waiters
                # Anything that escapes step() is ENGINE-fatal by
                # definition: request-scoped faults were contained
                # inside the step. Classify it as such for waiters
                # (EngineFatal is still a RuntimeError).
                if isinstance(e, EngineFatal):
                    fatal: BaseException = e
                else:
                    fatal = EngineFatal(
                        f"engine thread died: {type(e).__name__}: {e}")
                    fatal.__cause__ = e
                with self._wake:
                    self._engine_error = fatal
                    self._stop = True
                    for h in self._handles.values():
                        h._error = fatal
                        h._done.set()
                        h._tokens.put(_STREAM_END)
                    self._handles.clear()
                    self._wake.notify_all()
                return
            with self._wake:
                self._wake.notify_all()

    # Engine-thread callbacks ---------------------------------------------
    def _on_token(self, req: Request, tok: np.ndarray) -> None:
        with self._lock:
            h = self._handles.get(req.uid)
        if h is not None:
            h._tokens.put(np.asarray(tok))

    def _on_finish(self, req: Request) -> None:
        with self._lock:
            h = self._handles.pop(req.uid, None)
            self._completed.append(req)
        if h is not None:
            h._done.set()
            h._tokens.put(_STREAM_END)

    def _on_error(self, req: Request, err: BaseException) -> None:
        """Engine-thread callback for a request that FAILED (cancelled,
        deadline, contained fault): the handle's waiters get the typed
        error; the engine itself keeps serving."""
        with self._lock:
            h = self._handles.pop(req.uid, None)
            self._failed.append(req)
        if h is not None:
            h._error = err
            h._done.set()
            h._tokens.put(_STREAM_END)

    # Client surface -------------------------------------------------------
    def submit(self, prompt, max_new: int = 32, *,
               eos_id: Optional[int] = None, priority: float = 0.0,
               deadline_ticks: Optional[float] = None,
               max_ticks: Optional[int] = None,
               uid: Optional[int] = None) -> Submission:
        """Enqueue one request; returns its :class:`Submission` handle.

        Raises ``ValueError`` immediately (nothing enqueued) if the
        request could never be served (overlong, or worst-case KV blocks
        exceed the pool), and :class:`ServerOverloaded` (also nothing
        enqueued) when the shed policy rejects it at the door.
        ``priority`` orders admission (higher first, FIFO within a
        class); ``deadline_ticks`` is a per-request TTFT budget
        overriding the config SLO; ``max_ticks`` is a per-request
        decode-tick budget -- exceeding it fails the handle with
        :class:`RequestCancelled` and frees its slot/blocks."""
        r = Request(
            uid=next(self._auto_uid) if uid is None else uid,
            prompt=np.asarray(prompt), max_new=max_new, eos_id=eos_id,
            max_ticks=max_ticks,
        )
        self._srv._validate([r])
        h = Submission(r)
        with self._wake:
            if self._stop or self._engine_error is not None:
                raise RuntimeError("AsyncServer is shut down")
            if r.uid in self._handles:
                # Overwriting would orphan the first handle's waiter and
                # complete the second with the wrong Request.
                raise ValueError(
                    f"request uid={r.uid} is already in flight")
            self._handles[r.uid] = h
            try:
                self._srv.enqueue(
                    r, priority=priority, deadline_ticks=deadline_ticks)
            except ServerOverloaded:
                # Shed at the door: the handle never existed as far as
                # any waiter is concerned.
                del self._handles[r.uid]
                raise
            self._wake.notify_all()
        return h

    def cancel(self, handle: Submission) -> bool:
        """Cancel an outstanding submission from any thread.

        Returns True when the request was still in flight: the engine
        purges it at its next step boundary (queued entries before they
        cost a prefill; live slots with their KV blocks released and
        the ledger re-checked) and the handle's waiters get
        :class:`RequestCancelled` with any partial output on
        ``handle.request.out``. Returns False when the request already
        finished -- its result stands."""
        with self._wake:
            if self._handles.get(handle.uid) is not handle:
                return False
            self._srv.cancel(handle.uid)
            self._wake.notify_all()
        return True

    def stream(self, handle: Submission,
               timeout: Optional[float] = None) -> Iterator[np.ndarray]:
        return handle.stream(timeout=timeout)

    def drain(self, timeout: Optional[float] = None) -> List[Request]:
        """Block until all submitted requests finish; returns the ones
        completed since the last drain. Assumes no concurrent submits
        while draining (the metrics finalization runs on the caller)."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._wake:
            while self._handles or self._work_pending():
                if self._engine_error is not None:
                    raise self._engine_error
                left = None if deadline is None else (
                    deadline - time.perf_counter())
                if left is not None and left <= 0:
                    raise TimeoutError("drain() timed out")
                self._wake.wait(timeout=0.05 if left is None
                                else min(0.05, left))
            if self._engine_error is not None:
                raise self._engine_error
            out, self._completed = self._completed, []
        self._srv.finalize_metrics()
        return out

    def shutdown(self, *, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Graceful stop: optionally drain outstanding work, then stop
        and join the engine thread. With ``drain=False`` the engine
        aborts after its current step and any unfinished submissions
        fail with ``RuntimeError``. Idempotent.

        ``timeout`` bounds the WHOLE call: the drain and the thread
        join share one deadline (the join gets whatever budget the
        drain left), so ``shutdown(timeout=t)`` returns within ~t, not
        2t, even when both phases run long."""
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        if drain and self._started and self._engine_error is None:
            try:
                self.drain(timeout=timeout)
            except TimeoutError:
                pass  # fall through: stop anyway
        with self._wake:
            self._stop = True
            # Latch the queue too: a submit racing this teardown fails
            # in RequestQueue.push instead of feeding a dead engine.
            self._srv._queue.close()
            self._wake.notify_all()
        if self._started:
            left = (None if deadline is None
                    else max(0.0, deadline - time.perf_counter()))
            self._thread.join(timeout=left)
        with self._wake:
            if self._handles:
                err = RuntimeError(
                    "AsyncServer shut down before completion")
                for h in self._handles.values():
                    h._error = err
                    h._done.set()
                    h._tokens.put(_STREAM_END)
                self._handles.clear()

    close = shutdown

    def __enter__(self) -> "AsyncServer":
        self.start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(drain=exc_type is None)

    @property
    def metrics(self) -> ServeMetrics:
        return self._srv.metrics

    @property
    def failed(self) -> List[Request]:
        """Requests that failed (cancelled, deadline, contained fault)
        since construction; their typed errors sit on ``.error``."""
        with self._lock:
            return list(self._failed)

    @property
    def server(self) -> Server:
        """The wrapped engine (read-only use: metrics, config). Do not
        call its stepwise methods while the engine thread runs."""
        return self._srv
