"""Attention: GQA (chunked flash-style) and DeepSeek MLA, with KV caches.

Training/prefill use a double-scan online-softmax attention (bounded
VMEM/HBM working set at 32k sequence). Decode is single-token with a
functional KV cache. MLA decode uses the absorbed-matmul trick: attention
runs in the compressed-latent space so the cache stays (kv_lora + rope)
wide -- this is what makes deepseek-v3 decode_32k memory-feasible.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import modules as nn
from repro.models.layers import apply_rope, rmsnorm, rmsnorm_init

_NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, L, KV, hd)  [GQA]  or ckv (B, L, kv_lora) [MLA]
    v: jax.Array  # (B, L, KV, hd)  [GQA]  or k_rope (B, L, rope) [MLA]
    length: jax.Array  # int32 (B,): tokens already in cache, per slot


class PagedKVCache(NamedTuple):
    """Shared pool of fixed-size KV blocks (vLLM-style paging).

    Unlike :class:`KVCache` there is no per-slot ``max_len`` reservation:
    ``k``/``v`` are pools of ``num_blocks`` blocks of ``block_size`` rows
    shared by every serving slot, and a slot's rows live wherever its
    (host-managed) block table points. Block 0 is the NULL block: freed
    slots' table entries point at it so their masked decode writes land
    harmlessly. The block table itself is NOT part of the cache pytree --
    the server owns it host-side and passes it into each decode step,
    which keeps allocation pure numpy and the device cache donation-safe.
    """

    k: jax.Array  # (num_blocks, block_size, KV, hd) or (nb, bs, kv_lora)
    v: jax.Array  # (num_blocks, block_size, KV, hd) or (nb, bs, rope)
    length: jax.Array  # int32 (B,): tokens already in cache, per slot

    @property
    def block_size(self) -> int:
        return self.k.shape[1]


def _slot_lengths(cache, batch: int) -> jax.Array:
    """Per-slot lengths (B,). Accepts legacy scalar-length caches."""
    return jnp.broadcast_to(
        jnp.asarray(cache.length, jnp.int32), (batch,)
    )


def _paged_append(
    cache: PagedKVCache, block_tables: jax.Array,
    upd_k: jax.Array, upd_v: jax.Array,
) -> Tuple[PagedKVCache, jax.Array]:
    """Write one new row per slot into the pool (no view gather).

    block_tables: int32 (B, max_blocks) pool block ids (0 = unassigned /
    null). upd_k/upd_v: (B, ...) the decode step's new row per slot.
    Returns (new_cache, idx) with idx the pre-write lengths.
    """
    nb, bs = cache.k.shape[0], cache.k.shape[1]
    B, max_blocks = block_tables.shape
    idx = _slot_lengths(cache, B)  # (B,)
    # A live slot's current block is always assigned (the server grows
    # tables before the tick); dead slots clamp into their null row.
    slot_blk = jnp.minimum(idx // bs, max_blocks - 1)
    blk = jnp.take_along_axis(block_tables, slot_blk[:, None], axis=1)[:, 0]
    row = blk * bs + idx % bs  # (B,) flat pool rows, distinct for live slots
    kf = cache.k.reshape((nb * bs,) + cache.k.shape[2:])
    vf = cache.v.reshape((nb * bs,) + cache.v.shape[2:])
    kf = kf.at[row].set(upd_k.astype(kf.dtype))
    vf = vf.at[row].set(upd_v.astype(vf.dtype))
    new_cache = PagedKVCache(
        kf.reshape(cache.k.shape), vf.reshape(cache.v.shape), idx + 1
    )
    return new_cache, idx


def _paged_view(
    cache: PagedKVCache, block_tables: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Gather (B, max_blocks * block_size, ...) per-slot views of the
    pool -- the attn_kernel='gather' parity oracle. This materializes
    EVERY table entry (dead slots, blocks past the live length, null
    padding) in HBM; the paged Pallas kernel exists to never fetch
    those (kernels/paged_decode_attn.py). Rows gathered from unassigned
    table entries come from the null block and are masked off by the
    caller's validity mask.
    """
    nb, bs = cache.k.shape[0], cache.k.shape[1]
    B, max_blocks = block_tables.shape
    kf = cache.k.reshape((nb * bs,) + cache.k.shape[2:])
    vf = cache.v.reshape((nb * bs,) + cache.v.shape[2:])
    gather = (block_tables[:, :, None] * bs
              + jnp.arange(bs, dtype=jnp.int32)[None, None, :])
    flat_idx = gather.reshape(B, max_blocks * bs)
    return kf[flat_idx], vf[flat_idx]


def _paged_eff_lengths(idx: jax.Array, active) -> jax.Array:
    """Rows the paged kernel must attend over per slot, INCLUDING this
    tick's write: 0 for inactive slots, so the kernel fetches nothing
    for them (their residual deltas are gated off downstream anyway)."""
    eff = idx + 1
    if active is None:
        return eff
    return jnp.where(active.astype(jnp.float32) > 0, eff, 0)


def _advance_by(idx: jax.Array, S: int, advance) -> jax.Array:
    """New cache lengths after writing S rows; ``advance`` (int32 (B,))
    overrides S for bucketed prefill, where only the first ``advance[b]``
    of the padded rows are real."""
    if advance is None:
        return idx + S
    return idx + jnp.asarray(advance, jnp.int32)


def _scatter_rows(buf: jax.Array, upd: jax.Array, starts: jax.Array) -> jax.Array:
    """Write upd[b] into buf[b] at row offset starts[b].

    buf: (B, L, ...), upd: (B, S, ...), starts: int32 (B,). The per-slot
    start index is what makes continuous batching possible: every slot
    advances through its own sequence independently.
    """
    zeros = (jnp.zeros((), jnp.int32),) * (buf.ndim - 2)
    return jax.vmap(
        lambda b, u, s: jax.lax.dynamic_update_slice(b, u, (s,) + zeros)
    )(buf, upd.astype(buf.dtype), starts)


# =============================================================== GQA / MHA
def attn_init(key, cfg: ArchConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = nn.split_keys(key, 4)
    p = {
        "wq": nn.dense_init(ks[0], d, h * hd, dtype),
        "wk": nn.dense_init(ks[1], d, kv * hd, dtype),
        "wv": nn.dense_init(ks[2], d, kv * hd, dtype),
        "wo": nn.dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = nn.zeros_init((h * hd,), dtype)
        p["bk"] = nn.zeros_init((kv * hd,), dtype)
        p["bv"] = nn.zeros_init((kv * hd,), dtype)
    return p


def _flash_chunked(q, k, v, *, q_offset: int, chunk_q: int, chunk_k: int,
                   causal: bool = True):
    """Online-softmax attention. q:(B,Sq,H,D) k,v:(B,Sk,KV,D); H=g*KV.

    Scans q chunks (outer) and kv chunks (inner) carrying (acc, m, l).
    ``q_offset`` is the absolute position of q[0] (prefill continuation).
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    scale = D**-0.5
    nq = max(1, Sq // chunk_q)
    while Sq % nq:
        nq -= 1
    nk = max(1, Sk // chunk_k)
    while Sk % nk:
        nk -= 1
    cq, ck = Sq // nq, Sk // nk

    qc = q.reshape(B, nq, cq, KV, g, D)
    kc = k.reshape(B, nk, ck, KV, D)
    vc = v.reshape(B, nk, ck, KV, D)

    def q_step(_, qi):
        qblk, iq = qi  # (B, cq, KV, g, D), scalar index
        q_pos = q_offset + iq * cq + jnp.arange(cq)

        def kv_step(carry, kj):
            acc, m, l = carry
            kblk, vblk, jk = kj  # (B, ck, KV, D)
            k_pos = jk * ck + jnp.arange(ck)
            # Mixed precision (§Perf iteration sm-1): operands stay bf16
            # (half the HBM reads, MXU-rate dots), accumulate in f32.
            s = jnp.einsum(
                "bqkgd,bckd->bkgqc", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale  # (B, KV, g, cq, ck) f32
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bkgqc,bckd->bqkgd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, cq, KV, g, D), jnp.float32)
        m0 = jnp.full((B, KV, g, cq), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, g, cq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(nk)),
        )
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return None, out

    if nq == 1:
        # Single q block: no outer scan, no output stacking.
        _, out = q_step(None, (qc[:, 0], jnp.int32(0)))
        return out.reshape(B, Sq, H, D).astype(q.dtype)
    _, outs = jax.lax.scan(
        q_step, None, (qc.swapaxes(0, 1), jnp.arange(nq))
    )  # (nq, B, cq, KV, g, D)
    out = outs.swapaxes(0, 1).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def _default_chunks(S: int) -> Tuple[int, int]:
    """(chunk_q, chunk_k) for the double-scan flash attention.

    §Perf iterations qw-3/sm-3/ds-4 swept chunk_q up to S (outer scan
    removed): the cost_analysis memory term moved OPPOSITE to first-
    principles traffic because XLA counts a while body once -- a larger
    unscanned body surfaces bytes the chunked scan hides. We therefore
    size chunks on real-hardware reasoning (bounded f32 accumulator,
    fewer rescale rewrites than tiny chunks) and document the proxy
    artifact in EXPERIMENTS.md instead of chasing it."""
    return min(S, 512), min(S, 1024)


def gqa_forward(
    params,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    cache=None,
    block_tables: Optional[jax.Array] = None,
    advance: Optional[jax.Array] = None,
    attn_kernel: str = "gather",
    active: Optional[jax.Array] = None,
    continuation: bool = False,
    chunk_q: Optional[int] = None,
    chunk_k: Optional[int] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """x: (B, S, d). With cache and S==1 -> decode step.

    cache may be a contiguous :class:`KVCache` or a :class:`PagedKVCache`
    (decode only; prefill always targets a small contiguous cache that
    admission scatters into pool blocks). ``advance`` (int32 (B,)) is the
    bucketed-prefill true length: the cache length advances by it rather
    than by the padded S.

    ``continuation`` (static, batch=1 prefill only) marks a SUFFIX
    prefill behind an already-populated cache (prefix-cache admission):
    the fresh rows scatter at the cache length as usual, but attention
    runs the queries over the WHOLE cache buffer with ``q_offset`` at
    the prefix length, so suffix tokens attend over the cached prefix
    exactly as a full prefill would. Rows past the written tail are
    causally masked (exact-zero contributions), which is the same
    trailing-mask invariance the bucketed prefill relies on.

    ``attn_kernel`` selects the paged-decode implementation (static):
    'gather' materializes the full per-slot pool view then runs dense
    jnp attention (the parity oracle); 'paged' runs the fetch-skipping
    Pallas kernel straight out of the pool, never DMAing dead slots'
    blocks, blocks past the live length, or null padding entries.
    ``active`` (f32 (B,), serving only) marks live slots so the paged
    kernel can skip dead slots' fetches entirely.
    """
    B, S, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.dot(x, params["wq"])
    k = jnp.dot(x, params["wk"])
    v = jnp.dot(x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, S, kv, hd)
    v = v.reshape(B, S, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    dq, dk = _default_chunks(S)
    chunk_q = chunk_q or dq
    chunk_k = chunk_k or dk

    if cache is None:
        out = _flash_chunked(
            q, k, v, q_offset=0, chunk_q=min(chunk_q, S), chunk_k=min(chunk_k, S)
        )
        new_cache = None
    elif S == 1:
        # Decode: write k/v at each slot's own length, attend over that
        # slot's live prefix. Per-slot indices are what let the server
        # backfill a freed slot while its neighbours keep decoding.
        g = h // kv
        qd = q.reshape(B, kv, g, hd)
        ck = cv = None
        if isinstance(cache, PagedKVCache):
            if block_tables is None:
                raise ValueError("paged decode needs block_tables")
            new_cache, idx = _paged_append(
                cache, block_tables, k[:, 0], v[:, 0]
            )
            if attn_kernel != "paged":
                ck, cv = _paged_view(new_cache, block_tables)
        else:
            idx = _slot_lengths(cache, B)  # (B,)
            ck = _scatter_rows(cache.k, k, idx)
            cv = _scatter_rows(cache.v, v, idx)
            new_cache = KVCache(ck, cv, idx + 1)
        if ck is None:
            # Fetch-skipping kernel straight out of the pool: the
            # scalar-prefetched (tables, lengths) pair is the SASA
            # entry; only live blocks' pages are ever copied (PSRU).
            from repro.kernels import ops as kops
            o = kops.paged_decode_attn(
                qd, new_cache.k, new_cache.v, block_tables,
                _paged_eff_lengths(idx, active), scale=hd**-0.5,
            )
        else:
            L = ck.shape[1]
            # bf16 cache reads with f32 accumulation (no f32 cache copy).
            s = jnp.einsum(
                "bkgd,blkd->bkgl", qd, ck,
                preferred_element_type=jnp.float32
            ) * (hd**-0.5)
            valid = jnp.arange(L)[None, :] <= idx[:, None]  # (B, L)
            s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bkgl,blkd->bkgd", p.astype(cv.dtype), cv,
                           preferred_element_type=jnp.float32)
        out = o.reshape(B, 1, h, hd).astype(x.dtype)
    else:
        # Prefill into cache at each slot's current offset.
        if isinstance(cache, PagedKVCache):
            raise NotImplementedError(
                "prefill targets a small contiguous cache; admission "
                "scatters it into the pool (model.insert_slot_paged)"
            )
        idx = _slot_lengths(cache, B)
        ck = _scatter_rows(cache.k, k, idx)
        cv = _scatter_rows(cache.v, v, idx)
        if continuation:
            # Suffix prefill behind a cached prefix: attend q over the
            # whole buffer (prefix rows + freshly scattered suffix) with
            # the causal mask anchored at the prefix length. Batch=1 by
            # contract -- a traced per-slot q_offset would need per-row
            # masks instead of the shared one.
            assert B == 1, "continuation prefill is batch=1 (admission)"
            L = ck.shape[1]
            out = _flash_chunked(
                q, ck, cv, q_offset=idx[0],
                chunk_q=min(chunk_q, S), chunk_k=min(chunk_k, L),
            )
        else:
            out = _flash_chunked(
                q, k, v, q_offset=0,
                chunk_q=min(chunk_q, S), chunk_k=min(chunk_k, S),
            )
        new_cache = KVCache(ck, cv, _advance_by(idx, S, advance))

    y = jnp.dot(out.reshape(B, S, h * hd), params["wo"])
    if cfg.tp_axis is not None:
        # Serving TP: the heads above are this shard's 1/tp slice and wo
        # is row-sharded to match, so y is a partial sum -- the single
        # Megatron-style psum per attention block.
        y = jax.lax.psum(y, cfg.tp_axis)
    return y, new_cache


def gqa_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> KVCache:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return KVCache(
        k=jnp.zeros((batch, max_len, kv, hd), dtype),
        v=jnp.zeros((batch, max_len, kv, hd), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


def gqa_init_paged_cache(
    cfg: ArchConfig, batch: int, num_blocks: int, block_size: int, dtype
) -> PagedKVCache:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return PagedKVCache(
        k=jnp.zeros((num_blocks, block_size, kv, hd), dtype),
        v=jnp.zeros((num_blocks, block_size, kv, hd), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


# ===================================================================== MLA
def mla_init(key, cfg: ArchConfig, dtype):
    m = cfg.mla
    assert m is not None
    d, h = cfg.d_model, cfg.num_heads
    ks = nn.split_keys(key, 8)
    return {
        "wdq": nn.dense_init(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
        "wuq": nn.dense_init(
            ks[1], m.q_lora_rank, h * (m.qk_nope_dim + m.qk_rope_dim), dtype
        ),
        "wdkv": nn.dense_init(ks[2], d, m.kv_lora_rank, dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "wkr": nn.dense_init(ks[3], d, m.qk_rope_dim, dtype),
        "wuk": nn.dense_init(ks[4], m.kv_lora_rank, h * m.qk_nope_dim, dtype),
        "wuv": nn.dense_init(ks[5], m.kv_lora_rank, h * m.v_head_dim, dtype),
        "wo": nn.dense_init(ks[6], h * m.v_head_dim, d, dtype),
    }


def mla_forward(
    params,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    cache=None,
    block_tables: Optional[jax.Array] = None,
    advance: Optional[jax.Array] = None,
    attn_kernel: str = "gather",
    active: Optional[jax.Array] = None,
    continuation: bool = False,
    chunk_q: Optional[int] = None,
    chunk_k: Optional[int] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    m = cfg.mla
    if continuation:
        # Prefix-cache suffix prefill needs bucketed (masked-tail)
        # prefill to be exact, which excludes every MLA family (moe
        # capacity routing is batch-shape dependent); the server gates
        # prefix_cache on bucketable_families() before it gets here.
        raise NotImplementedError(
            "continuation prefill is not supported for MLA attention"
        )
    B, S, d = x.shape
    dq_, dk_ = _default_chunks(S)
    chunk_q = chunk_q or dq_
    chunk_k = chunk_k or dk_
    h = cfg.num_heads
    nope, rope_d, vd = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim

    cq = rmsnorm(params["q_norm"], jnp.dot(x, params["wdq"]), cfg.norm_eps)
    q = jnp.dot(cq, params["wuq"]).reshape(B, S, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = rmsnorm(params["kv_norm"], jnp.dot(x, params["wdkv"]), cfg.norm_eps)
    kr = apply_rope(
        jnp.dot(x, params["wkr"])[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]  # (B, S, rope_d), shared across heads

    if cache is None or S > 1:
        # Train/prefill: decompress and run chunked flash with KV=H.
        k_nope = jnp.dot(ckv, params["wuk"]).reshape(B, S, h, nope)
        v = jnp.dot(ckv, params["wuv"]).reshape(B, S, h, vd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :], (B, S, h, rope_d))], -1
        )
        qq = jnp.concatenate([q_nope, q_rope], -1)
        # Pad v to qk head dim for the shared flash kernel, slice after.
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, (nope + rope_d) - vd)))
        out = _flash_chunked(
            qq, k, v_pad, q_offset=0,
            chunk_q=min(chunk_q, S), chunk_k=min(chunk_k, S),
        )[..., :vd]
        new_cache = None
        if cache is not None:
            if isinstance(cache, PagedKVCache):
                raise NotImplementedError(
                    "prefill targets a small contiguous cache; admission "
                    "scatters it into the pool (model.insert_slot_paged)"
                )
            idx = _slot_lengths(cache, B)
            cc = _scatter_rows(cache.k, ckv, idx)
            cr = _scatter_rows(cache.v, kr, idx)
            new_cache = KVCache(cc, cr, _advance_by(idx, S, advance))
    else:
        # Absorbed decode: attention in the compressed latent space.
        wuk = params["wuk"].reshape(m.kv_lora_rank, h, nope)
        # q_latent[b,h,r] = sum_n q_nope[b,h,n] * wuk[r,h,n]
        # bf16 operands, f32 accumulation (no f32 cache copies).
        q_lat = jnp.einsum(
            "bhn,rhn->bhr", q_nope[:, 0], wuk,
            preferred_element_type=jnp.float32,
        )
        cc = cr = None
        if isinstance(cache, PagedKVCache):
            if block_tables is None:
                raise ValueError("paged decode needs block_tables")
            new_cache, idx = _paged_append(
                cache, block_tables, ckv[:, 0], kr[:, 0]
            )
            if attn_kernel != "paged":
                cc, cr = _paged_view(new_cache, block_tables)
        else:
            idx = _slot_lengths(cache, B)  # (B,)
            cc = _scatter_rows(cache.k, ckv, idx)
            cr = _scatter_rows(cache.v, kr, idx)
            new_cache = KVCache(cc, cr, idx + 1)
        if cc is None:
            # Absorbed decode straight out of the latent pool: the
            # kernel's scores AND context stay (kv_lora + rope) wide,
            # and only live table blocks are ever DMA'd.
            from repro.kernels import ops as kops
            ctx_lat = kops.paged_mla_decode_attn(
                q_lat.astype(cache.k.dtype), q_rope[:, 0],
                new_cache.k, new_cache.v, block_tables,
                _paged_eff_lengths(idx, active),
                scale=(nope + rope_d) ** -0.5,
            )
        else:
            L = cc.shape[1]
            s = (
                jnp.einsum("bhr,blr->bhl", q_lat.astype(cc.dtype), cc,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bhr,blr->bhl", q_rope[:, 0], cr,
                             preferred_element_type=jnp.float32)
            ) * ((nope + rope_d) ** -0.5)
            valid = jnp.arange(L)[None, :] <= idx[:, None]  # (B, L)
            s = jnp.where(valid[:, None, :], s, _NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            ctx_lat = jnp.einsum("bhl,blr->bhr", p.astype(cc.dtype), cc,
                                 preferred_element_type=jnp.float32)
        wuv = params["wuv"].reshape(m.kv_lora_rank, h, vd)
        out = jnp.einsum("bhr,rhv->bhv", ctx_lat.astype(wuv.dtype), wuv,
                         preferred_element_type=jnp.float32)
        out = out[:, None].astype(x.dtype)  # (B, 1, h, vd)

    y = jnp.dot(out.reshape(B, S, h * vd).astype(x.dtype), params["wo"])
    if cfg.tp_axis is not None:
        # Serving TP: wuq/wuk/wuv are head-sliced and wo row-sharded; the
        # latent cache itself is replicated over 'model' (wdkv/wkr are),
        # so one psum here closes the whole absorbed-decode block.
        y = jax.lax.psum(y, cfg.tp_axis)
    return y, new_cache


def mla_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> KVCache:
    m = cfg.mla
    return KVCache(
        k=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        v=jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


def mla_init_paged_cache(
    cfg: ArchConfig, batch: int, num_blocks: int, block_size: int, dtype
) -> PagedKVCache:
    m = cfg.mla
    return PagedKVCache(
        k=jnp.zeros((num_blocks, block_size, m.kv_lora_rank), dtype),
        v=jnp.zeros((num_blocks, block_size, m.qk_rope_dim), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )
