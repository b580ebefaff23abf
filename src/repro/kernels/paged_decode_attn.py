"""Paged decode attention: fetch-skipping Pallas kernels straight out of
the shared KV pool.

A decode tick attends each slot over its own live rows of a paged pool.
What is dead -- inactive slots, blocks past each request's live length,
null table padding -- is known from METADATA alone (block tables and
per-slot lengths), which is the paper's dynamic-sparsity setting: so the
kernels never fetch it, rather than masking it after the fetch.

GQA (``paged_gqa_decode_attn``, the served kernel): one grid step per
slot, ``grid=(B,)``, run in order. The K and V pools stay in HBM
(``memory_space=pl.ANY``); the step walks the slot's live table prefix
in chunks of ``P`` pool blocks, copying each block's K and V pages with
its own DMA into a double buffer, so chunk ``c+1`` (or the next slot's
first chunk) is in flight while chunk ``c`` is computed. Mapping onto
the paper's microarchitecture:

  * **SASA entry** -- the scalar-prefetched ``(block_tables, lengths)``
    pair lives in SMEM before the body runs: the chunk count
    ``ceil(ceil(len / bs) / P)`` and every page address are resolved
    before any operand fetch, like the SASA table consulted at fetch.
  * **PSRU** -- the skip happens before the fetch: a page copy is issued
    only for a live table entry (the last chunk copies only its live
    blocks), and an inactive slot issues none and writes zeros. A dead
    block or slot costs neither a DMA nor a grid step;
    :func:`gqa_chunk_fetch_ids` mirrors the copies on the host.
  * **SpRF** -- the online-softmax statistics (m, l, acc) carried across
    one slot's chunks are the running state the skip must not corrupt:
    rows past the live length and buffer rows of pages the last chunk
    did not copy (stale, or NaN when fresh) are selected out of the
    scores and zeroed in V, since 0 * NaN in the PV dot is NaN.

The pool ``(nb, bs, KV, D)`` is read as ``(nb, bs*KV*D/W, W)`` lane
rows (:func:`gqa_lane_view`): a reshape of each contiguous block, so a
page copy moves the block's own bytes, with no ``(KV, D)`` tile padding.
Each lane row holds ``n = W // D`` head vectors; one 2-D dot per lane
offset scores every query row against every (row, head) vector of the
chunk, and a head mask keeps each query on its own KV head. ``P`` comes
from the call's shapes alone (:func:`gqa_chunk_blocks`).

MLA (``paged_mla_decode_attn``, DeepSeek absorbed decode: scores and
context both in the compressed latent space over (nb, bs, r) / (nb, bs,
rope) pools) keeps the earlier structure: grid ``(B, max_blocks)`` with
the block axis fastest, its BlockSpec index map CLAMPING dead grid steps
onto the slot's last live block (the block index stops changing, so the
pipeline issues no further DMA), ``pl.when`` predicating the compute,
and :func:`clamped_block_ids` mirroring the clamp on the host.

Both kernels are validated in interpret mode against the gather oracles
in ``kernels/ref.py``; ``kernels/ops.py`` holds the ragged-shape
wrappers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _last_live_block(length, block_size: int):
    """Ordinal (0-based, within the slot's table) of the last live block.

    Slots with length 0 clamp onto table entry 0 -- the server keeps a
    dead slot's whole table row at the null block, so the (single,
    possibly elided) fetch lands on null rows, never on a freed block.
    """
    return jnp.maximum((length + block_size - 1) // block_size - 1, 0)


def clamped_block_ids(
    block_tables: np.ndarray, lengths: np.ndarray, block_size: int
) -> np.ndarray:
    """Host-side mirror of the MLA kernel's index-map math: the pool
    block id grid step (b, j) maps to, for every j in the table width.

    This is the fetch-elision contract in closed form -- tests enumerate
    it to prove that no grid step can ever name a block outside the
    slot's live table prefix (or, for a dead slot, its entry 0): the DMA
    for a skipped block is not masked after the fact, it is never
    addressed in the first place.
    """
    tbl = np.asarray(block_tables)
    ln = np.asarray(lengths)
    B, max_blocks = tbl.shape
    last = np.maximum(-(-ln // block_size) - 1, 0)  # (B,)
    j = np.arange(max_blocks)[None, :]
    jj = np.minimum(j, last[:, None])
    return np.take_along_axis(tbl, jj, axis=1)


# ============================================================== GQA kernel
_LANES = 128
_CHUNK_ROWS = 256  # pool rows (tokens) one chunk aims to cover
_CHUNK_VMEM_BYTES = 8 << 20  # the two K and two V chunk buffers together


def gqa_lane_view(block_size: int, kv_heads: int, head_dim: int):
    """``(W, n)``: the kernel reads each pool block as ``(bs*KV*D/W, W)``
    lane rows of width ``W``, ``n = W // D`` head vectors to a row.

    ``W`` is 128 where a block fills whole 128-lane rows and ``D``
    divides 128 (smollm's ``D = 64`` packs two vectors a row), else
    ``D`` itself. The view is a reshape of the contiguous block, so the
    DMA moves the block's bytes and no tile padding of ``(KV, D)``.
    """
    if (_LANES % head_dim == 0
            and (block_size * kv_heads * head_dim) % _LANES == 0):
        return _LANES, _LANES // head_dim
    return head_dim, 1


def gqa_chunk_blocks(max_blocks: int, block_size: int, kv_heads: int,
                     head_dim: int, itemsize: int) -> int:
    """Pool blocks per chunk, ``P``, from the call's shapes alone: about
    ``_CHUNK_ROWS`` rows, at most the table width, and few enough that
    the double-buffered K and V chunks fit ``_CHUNK_VMEM_BYTES`` (lane
    rows padded to 16, lanes to 128)."""
    W, _ = gqa_lane_view(block_size, kv_heads, head_dim)
    rows = block_size * kv_heads * head_dim // W
    blk = -(-rows // 16) * 16 * -(-W // _LANES) * _LANES * itemsize
    p = max(1, min(_CHUNK_ROWS // block_size, max_blocks))
    while p > 1 and 4 * p * blk > _CHUNK_VMEM_BYTES:
        p //= 2
    return p


def _gqa_kernel(tbl_ref, len_ref, hid_ref, q_ref, k_hbm, v_hbm, o_ref,
                kbuf, vbuf, sems, base_ref, *, block_size: int,
                chunk_blocks: int, kv_heads: int, group: int,
                head_dim: int, scale: float):
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    bs, P, KV, g, D = block_size, chunk_blocks, kv_heads, group, head_dim
    max_blocks = tbl_ref.shape[1]
    n, Q, W = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    Vb = k_hbm.shape[1]  # lane rows per pool block
    C = P * Vb  # lane rows per chunk

    def live_blocks(slot):
        return pl.cdiv(len_ref[slot], bs)

    def chunk_copies(slot, c, buf):
        """Chunk ``c`` of ``slot``'s (K, V) page copies into buffer
        ``buf``, each with the predicate that it names a live block."""
        left = live_blocks(slot) - c * P
        for p in range(P):
            blk = tbl_ref[slot, jnp.minimum(c * P + p, max_blocks - 1)]
            dst = pl.ds(p * Vb, Vb)
            yield p < left, (
                pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[buf, dst],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[buf, dst],
                                      sems.at[1, buf]))

    def start_chunk(slot, c, buf):
        for live, copies in chunk_copies(slot, c, buf):
            @pl.when(live)
            def _start():
                for cp in copies:
                    cp.start()

    def wait_chunk(slot, c, buf):
        for live, copies in chunk_copies(slot, c, buf):
            @pl.when(live)
            def _wait():
                for cp in copies:
                    cp.wait()

    nxt_slot = jnp.minimum(b + 1, n_slots - 1)
    has_next = b + 1 < n_slots

    @pl.when(b == 0)
    def _first():
        base_ref[0] = 0
        start_chunk(0, 0, 0)

    length = len_ref[b]
    n_chunks = pl.cdiv(live_blocks(b), P)
    base = base_ref[0]
    qrow = jax.lax.broadcasted_iota(jnp.int32, (Q, C), 0)

    def body(c, carry):
        m_prev, l_prev, acc_prev = carry
        buf = (base + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _prefetch_next_chunk():
            start_chunk(b, c + 1, 1 - buf)

        @pl.when(jnp.logical_and(c + 1 == n_chunks, has_next))
        def _prefetch_next_slot():
            start_chunk(nxt_slot, 0, 1 - buf)

        wait_chunk(b, c, buf)
        live_rows = length - c * P * bs  # may exceed the chunk

        @pl.when(live_rows < P * bs)
        def _zero_dead_v():
            # Zero every V element past the live rows: pages the last
            # chunk did not copy hold stale (or uninitialised) VMEM, and
            # 0 * NaN in the PV dot is NaN.
            flat = (jax.lax.broadcasted_iota(jnp.int32, (C, W), 0) * W
                    + jax.lax.broadcasted_iota(jnp.int32, (C, W), 1))
            vbuf[buf] = jnp.where(flat < live_rows * KV * D, vbuf[buf], 0)

        k, v = kbuf[buf], vbuf[buf]  # (C, W)
        s = []
        for e in range(n):
            se = jax.lax.dot_general(
                q_ref[0, e], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (Q, C)
            head = hid_ref[e]  # (1, C): KV head of column's vector
            vec = jax.lax.broadcasted_iota(jnp.int32, (Q, C), 1) * n + e
            ok = ((head * g <= qrow) & (qrow < head * g + g)
                  & (vec < live_rows * KV))
            s.append(jnp.where(ok, se, _NEG_INF))
        m_new = m_prev
        for se in s:
            m_new = jnp.maximum(m_new, jnp.max(se, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr
        acc_new = acc_prev * corr
        for e, se in enumerate(s):
            pe = jnp.exp(se - m_new)
            l_new = l_new + jnp.sum(pe, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                pe.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # (Q, W)
            if n > 1:  # keep the lanes that hold vector e of each row
                lane = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1)
                pv = jnp.where((e * D <= lane) & (lane < e * D + D), pv, 0.0)
            acc_new = acc_new + pv
        return m_new, l_new, acc_new

    init = (jnp.full((Q, 1), _NEG_INF, jnp.float32),
            jnp.zeros((Q, 1), jnp.float32),
            jnp.zeros((Q, W), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, init)

    @pl.when(jnp.logical_and(n_chunks == 0, has_next))
    def _dead_slot_prefetch():
        start_chunk(nxt_slot, 0, base)

    base_ref[0] = (base + n_chunks) % 2
    out = acc[:, :D]
    for e in range(1, n):
        out = out + acc[:, e * D:(e + 1) * D]
    o_ref[0] = (out / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_gqa_decode_attn(
    q: jax.Array,  # (B, KV, g, D) grouped query heads
    k_pool: jax.Array,  # (nb, bs, KV, D) shared pool keys
    v_pool: jax.Array,  # (nb, bs, KV, D) shared pool values
    block_tables: jax.Array,  # int32 (B, max_blocks), 0 = null block
    lengths: jax.Array,  # int32 (B,) live rows incl. this tick's write
    *,
    scale: float | None = None,
    interpret: bool | pltpu.InterpretParams = False,
) -> jax.Array:
    """(B, KV, g, D) attention over each slot's live pool blocks.

    ``lengths[b] == 0`` marks an inactive slot: no block of its table is
    fetched or dotted and its output rows are zero (the serving engine
    gates dead slots' residual deltas anyway). ``interpret`` also takes
    ``pltpu.InterpretParams``, as the race and NaN-memory tests do.
    """
    B, KV, g, D = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    max_blocks = block_tables.shape[1]
    scale = scale if scale is not None else D**-0.5
    W, n = gqa_lane_view(bs, KV, D)
    Vb = bs * KV * D // W
    P = gqa_chunk_blocks(max_blocks, bs, KV, D, k_pool.dtype.itemsize)
    Q = KV * g
    # Query e of a lane row sits in lanes [e*D, (e+1)*D); zeros elsewhere.
    q2 = q.reshape(B, Q, D)
    qx = jnp.stack([jnp.pad(q2, ((0, 0), (0, 0), (e * D, W - D - e * D)))
                    for e in range(n)], axis=1)  # (B, n, Q, W)
    vec = np.arange(P * Vb)[None, :] * n + np.arange(n)[:, None]
    hid = jnp.asarray((vec % KV)[:, None, :], jnp.int32)  # (n, 1, C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((n, 1, P * Vb), lambda b, t, ln: (0, 0, 0)),
            pl.BlockSpec((1, n, Q, W), lambda b, t, ln: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Q, D), lambda b, t, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P * Vb, W), k_pool.dtype),
            pltpu.VMEM((2, P * Vb, W), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _gqa_kernel, block_size=bs, chunk_blocks=P, kv_heads=KV, group=g,
        head_dim=D, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), hid,
      qx, k_pool.reshape(nb, Vb, W),
      v_pool.reshape(nb, Vb, W))
    return out.reshape(B, KV, g, D)


# ============================================================== MLA kernel
def _mla_kernel(tbl_ref, len_ref, ql_ref, qr_ref, ckv_ref, kr_ref, o_ref,
                acc_ref, m_ref, l_ref, *, block_size: int, n_blocks: int,
                scale: float):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    start = j * block_size
    live = start < length

    @pl.when(live)
    def _compute():
        ql = ql_ref[0]  # (h, r) latent-absorbed queries
        qr = qr_ref[0]  # (h, rope)
        ckv = ckv_ref[0]  # (bs, r) compressed latents
        kr = kr_ref[0]  # (bs, rope) shared rope keys
        # Scores in the latent space: the two dot products sum BEFORE
        # the scale, mirroring the gather path's (e1 + e2) * scale.
        s = (
            jax.lax.dot_general(
                ql, ckv, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                qr, kr, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        ) * scale  # (h, bs) f32
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        ctx = jax.lax.dot_general(
            p.astype(ckv.dtype), ckv,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (h, r) context still in the latent space
        acc_ref[...] = acc_ref[...] * corr[..., None] + ctx
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_mla_decode_attn(
    q_lat: jax.Array,  # (B, h, r) wuk-absorbed queries
    q_rope: jax.Array,  # (B, h, rope)
    ckv_pool: jax.Array,  # (nb, bs, r) compressed-latent pool
    kr_pool: jax.Array,  # (nb, bs, rope) shared rope-key pool
    block_tables: jax.Array,  # int32 (B, max_blocks)
    lengths: jax.Array,  # int32 (B,)
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """(B, h, r) latent-space context over each slot's live pool blocks.

    The caller applies ``wuv`` to decompress -- attention itself never
    leaves the compressed space (the absorbed-decode trick), so the
    fetched bytes per block are (r + rope) wide, not heads x head_dim.
    """
    B, h, r = q_lat.shape
    rope = q_rope.shape[-1]
    nb, bs = ckv_pool.shape[0], ckv_pool.shape[1]
    max_blocks = block_tables.shape[1]

    def ckv_index(b, j, tbl_ref, len_ref):
        jj = jnp.minimum(j, _last_live_block(len_ref[b], bs))
        return (tbl_ref[b, jj], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda b, j, t, ln: (b, 0, 0)),
            pl.BlockSpec((1, h, rope), lambda b, j, t, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, r), ckv_index),
            pl.BlockSpec((1, bs, rope), ckv_index),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda b, j, t, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, r), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_kernel, block_size=bs, n_blocks=max_blocks, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, r), q_lat.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat, q_rope, ckv_pool, kr_pool)


# ======================================================= savings accounting
def decode_attn_block_counts(
    lengths, max_blocks: int, block_size: int
) -> tuple[int, int]:
    """(fetched, total) pool blocks one decode tick touches, in
    block-table units -- the successor of the retired contiguous
    prototype's tile accounting.

    ``lengths`` are per-slot live rows INCLUDING this tick's write (0 =
    inactive slot). ``total`` is what the gather path materializes: the
    full ``max_blocks`` view for every slot, dead or alive; ``fetched``
    is what the paged kernel DMAs: ``ceil(len / block_size)`` live
    blocks per slot and nothing for inactive slots.

    ``fetched`` is exact for the GQA kernel: it issues one K and one V
    page copy per live block and none for an inactive slot (see
    :func:`gqa_chunk_fetch_ids`).
    """
    ln = np.asarray(lengths, np.int64)
    fetched = int(np.sum(-(-np.maximum(ln, 0) // block_size)))
    return fetched, int(ln.shape[0]) * int(max_blocks)


def decode_attn_chunks(lengths, block_size: int, chunk_blocks: int) -> int:
    """Chunk steps the GQA kernel runs in one call: ``ceil(ceil(len /
    block_size) / chunk_blocks)`` per slot, none for an inactive slot."""
    ln = np.maximum(np.asarray(lengths, np.int64), 0)
    return int(np.sum(-(-(-(-ln // block_size)) // chunk_blocks)))


def gqa_chunk_fetch_ids(block_tables, lengths, block_size: int,
                        chunk_blocks: int) -> list[list[list[int]]]:
    """Host mirror of the GQA kernel's page copies, in the kernel's own
    index math: for each slot, its chunks in order, each the pool block
    ids whose K and V pages the chunk copies. Chunk ``c`` page ``p``
    reads table entry ``min(c*P + p, max_blocks - 1)`` and is issued
    only while ``p < live_blocks - c*P``; an inactive slot runs no
    chunk. Tests enumerate it to prove that no copy ever names a table
    entry outside the slot's live prefix."""
    tbl = np.asarray(block_tables)
    max_blocks, P = tbl.shape[1], chunk_blocks
    out = []
    for b, n in enumerate(np.asarray(lengths)):
        live = -(-int(n) // block_size)
        out.append([
            [int(tbl[b, min(c * P + p, max_blocks - 1)])
             for p in range(P) if p < live - c * P]
            for c in range(-(-live // P))])
    return out


def decode_attn_savings(lengths, max_blocks: int, block_size: int) -> float:
    """Fraction of pool-block fetches (fetch+compute) the paged kernel
    skips vs the full-view gather -- the paper's 'redundant ops' metric
    for the serving cache, in block-table units."""
    fetched, total = decode_attn_block_counts(lengths, max_blocks,
                                              block_size)
    if total == 0:
        return 0.0
    return 1.0 - fetched / total
