"""The served Pallas kernels compile for a TPU v5e at published widths.

Interpret mode cannot show what the chip's compiler refuses (block
shapes off the (8, 128) tiling, unaligned DMA slices, too much fast
memory), so each kernel is compiled here for a described ``v5e:2x2``
chip, shapes only, nothing runs. The topology is described inside a
module fixture: only the worker that runs this file loads the TPU
library, and where it cannot be described every test here skips.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kops

SMOLLM = get_config("smollm-135m")
DEEPSEEK = get_config("deepseek-v3-671b")
NEMO = get_config("mistral-nemo-12b")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(one_chip, monkeypatch):
    """Kernels compiled (not interpreted) for the described chip, with
    the persistent compilation cache off: what is written for a chip
    that is not attached cannot be read back. Returns a function that
    compiles ``fn`` at the given (shape, dtype) pairs and returns the
    compiled HLO text."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)


BF16 = jnp.bfloat16


@pytest.mark.parametrize("k,f,block_m", [
    (SMOLLM.d_model, SMOLLM.d_ff, 8),  # served: one decode tile of 8 slots
    (SMOLLM.d_model, SMOLLM.d_ff, 1),  # one slot per tile
    (NEMO.d_model, NEMO.d_ff // 4, 8),  # one TP-4 shard: the VMEM limit
])
def test_glu_mlp_compiles(compile_for_tpu, k, f, block_m):
    hlo = compile_for_tpu(
        lambda x, wg, wi, wo: kops.sparce_glu_mlp_fused(
            x, wg, wi, wo, block_m=block_m, block_f=128, act="silu",
            tau=0.0),
        ((8, k), BF16), ((k, f), BF16), ((k, f), BF16), ((f, k), BF16))
    assert "tpu_custom_call" in hlo


def test_relu_mlp_compiles(compile_for_tpu):
    k, f = SMOLLM.d_model, SMOLLM.d_ff
    hlo = compile_for_tpu(
        lambda x, wi, wo: kops.sparce_mlp_fused(
            x, wi, wo, block_m=8, block_f=128, act="relu"),
        ((8, k), BF16), ((k, f), BF16), ((f, k), BF16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [BF16, jnp.float32])
def test_relu_bitmap_compiles(compile_for_tpu, dtype):
    hlo = compile_for_tpu(
        lambda h: kops.relu_with_bitmap(h, (8, 128)),
        ((64, SMOLLM.d_ff), dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv,g,hd,B,max_blocks", [
    # per-chip shapes of the benchmark's cells, at their B and table width
    (SMOLLM.num_kv_heads, SMOLLM.num_heads // SMOLLM.num_kv_heads,
     SMOLLM.resolved_head_dim, 128, 128),  # smollm-135m: max_len 2048
    (NEMO.num_kv_heads, NEMO.num_heads // NEMO.num_kv_heads,
     NEMO.resolved_head_dim, 64, 96),  # mistral-nemo-12b-s8: 1536
    (NEMO.num_kv_heads // 4, NEMO.num_heads // NEMO.num_kv_heads,
     NEMO.resolved_head_dim, 32, 128),  # mistral-nemo-12b-tp4, one chip
], ids=["smollm-135m", "mistral-nemo-12b-s8", "mistral-nemo-12b-tp4"])
def test_paged_gqa_decode_compiles(compile_for_tpu, kv, g, hd, B,
                                   max_blocks):
    bs = 16
    nb = B * max_blocks + 1
    hlo = compile_for_tpu(
        lambda q, k, v, t, n: kops.paged_decode_attn(
            q, k, v, t, n, scale=hd ** -0.5),
        ((B, kv, g, hd), BF16), ((nb, bs, kv, hd), BF16),
        ((nb, bs, kv, hd), BF16), ((B, max_blocks), jnp.int32),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_mla_decode_compiles(compile_for_tpu):
    m = DEEPSEEK.mla
    h, r, rope = DEEPSEEK.num_heads, m.kv_lora_rank, m.qk_rope_dim
    B, bs, max_blocks = 8, 16, 16
    nb = B * max_blocks + 1
    hlo = compile_for_tpu(
        lambda ql, qr, c, kr, t, n: kops.paged_mla_decode_attn(
            ql, qr, c, kr, t, n, scale=(m.qk_nope_dim + rope) ** -0.5),
        ((B, h, r), BF16), ((B, h, rope), BF16), ((nb, bs, r), BF16),
        ((nb, bs, rope), BF16), ((B, max_blocks), jnp.int32),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo
